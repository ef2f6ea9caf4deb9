"""round_mfu: model operations of the rounds completed in the window (the
configuration's family count from ``bench/flops``), over window seconds and
the chip's bf16 peak. Forward + backward of the clients that trained;
recomputation is not counted."""
from bench import harness


def read(record, trace, peak):
    if not record.get("rounds"):
        return None
    per_round = harness.flops(record["conf"]).train_per_round(record["conf"], record["cell"])
    return per_round * record["rounds"] / record["window_s"] / peak["bf16_flops_per_s"]
