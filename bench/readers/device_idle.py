"""1 - (union of device-op intervals) / traced window, worst device, in %."""
from mxbench import reduce_trace


def read(run, args):
    trace = run["trace"]
    if trace is None or not trace["devices"]:
        return None
    window = reduce_trace.window_s(trace)
    if not window:
        return None
    busy = min(reduce_trace.busy_s(d) for d in trace["devices"])
    return 100.0 * (1.0 - busy / window)
