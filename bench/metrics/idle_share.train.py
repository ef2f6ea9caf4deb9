"""idle_share.train: 1 - device busy / window, from the trace of a training
cell's window."""


def read(record, trace, peak):
    return None if trace is None else trace.idle_share
