"""round_host_ms: median milliseconds of a round's host time: its
``fl.round`` span (scheduling, dispatch, report, checkpoint) less its
``fl.device_wait`` child (the loss read back), over the window's rounds.

Read from the program's own span ring (``repro.core.telemetry``) once
``drive`` has returned: the window's rounds are the last
``record["rounds"]`` ``fl.round`` spans (no round runs after the window).
None when the ring holds fewer, or the program has no ring.
"""


def read(record, trace, peak):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    rounds = telemetry.last("fl.round", record.get("rounds"))
    if rounds is None:
        return None
    kids = telemetry.children()
    return telemetry.median_ms(telemetry.self_ns(r, kids, {"fl.device_wait"}) for r in rounds)
