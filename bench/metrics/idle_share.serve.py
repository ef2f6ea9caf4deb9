"""idle_share.serve: 1 - device busy / window, from the trace of a serving
cell's window."""


def read(record, trace, peak):
    return None if trace is None else trace.idle_share
