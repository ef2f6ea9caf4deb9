"""service_ms: median milliseconds from the first byte of a request's INFER
frame to its RESULT sent (the service's ``serve.request`` span), over the
requests of the window's batches: the service's part of a request's
latency, without the wire to and from the client.

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
    return telemetry.median_ms(s.ns for s in telemetry.spans("serve.request") if s.id in ids)
