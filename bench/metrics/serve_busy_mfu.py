"""serve_busy_mfu: forward operations of the real (non-padding) images the
service ran in the window, over the device's busy seconds and the bf16
peak. Taken over busy time: at a fixed offered rate a wall-clock share is
set by the rate alone."""
from bench import harness


def read(record, trace, peak):
    if trace is None or trace.busy_s <= 0 or not record.get("images_served"):
        return None
    conf = record["conf"]
    per_image = harness.flops(conf).forward_per_image(conf, conf["img_size"])
    return record["images_served"] * per_image / trace.busy_s / peak["bf16_flops_per_s"]
