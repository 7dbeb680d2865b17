"""Device idle share of the traced window: 1 - (union of the intervals
in which a device operation ran) / window, from the profiler trace."""


def read(run):
    if not run.get("trace_window_s") or not run.get("busy_s"):
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["trace_window_s"])
