"""recv_ms: median milliseconds from the first byte of a request's INFER
frame to its frame parsed and enqueued (the service's ``serve.recv`` span),
over the requests of the window's batches.

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
    ids = {i for b in batches for i in b.ids}
    return telemetry.median_ms(s.ns for s in telemetry.spans("serve.recv") if s.id in ids)
