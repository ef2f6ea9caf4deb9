"""batch_host_ms: median milliseconds of a batch's host time: its
``serve.batch`` span (linger, pad, H2D, dispatch, decode, send) less its
``serve.device_wait`` child, over the window's batches.

Read from the program's own span ring (``repro.core.telemetry``) once
``drive`` has returned. The window's batches are the last ``record["batches"]``
``serve.batch`` spans: the drain after the window (about 1-2 batches at 96
req/s) stands in for as many batches at its start. None when the ring holds
fewer batches, or the program has no ring.
"""


def read(record, trace, peak):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    batches = telemetry.last("serve.batch", record.get("batches"))
    if batches is None:
        return None
    kids = telemetry.children()
    return telemetry.median_ms(telemetry.self_ns(b, kids, {"serve.device_wait"}) for b in batches)
