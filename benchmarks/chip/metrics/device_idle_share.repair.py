"""Share of the traced window in which no operation ran on the device,
in the node-repair cells. Source: the profiler's device trace
(trace_reduce)."""


def read(r):
    return r.trace.idle_percent() if r.trace is not None else None
