"""frame_ready_share: the share of the window's requests whose frame had
crossed to the device by the time their batch closed (the ``ids`` of each
batch's ``serve.staged`` mark over the requests of its ``serve.batch``).

Read from the program's own span ring (``repro.core.telemetry``) once
``drive`` has returned. The window's batches are the last ``record["batches"]``
``serve.batch`` spans: the drain after the window (about 1-2 batches at 96
req/s) stands in for as many batches at its start. None when the ring holds
fewer batches, when a batch has no ``serve.staged`` mark (a program that
does not stage frames ahead), or the program has no ring.
"""


def read(record, trace, peak):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    batches = telemetry.last("serve.batch", record.get("batches"))
    if batches is None:
        return None
    marks = {s.parent: s for s in telemetry.spans("serve.staged")}
    if any(b.seq not in marks for b in batches):
        return None
    return sum(len(marks[b.seq].ids) for b in batches) / sum(len(b.ids) for b in batches)
