"""device_idle_share.stream (model step and device): 1 - the union of
device-operation intervals over the traced window, from the profiler
trace."""


def read(run):
    if run.trace_summary is None:
        return None
    return run.trace_summary["idle_share"]
