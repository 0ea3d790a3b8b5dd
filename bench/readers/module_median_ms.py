"""Median device duration, in ms, of the executed programs (the trace's
``XLA Modules`` line) whose name matches, busiest device."""
import statistics

from mxbench import reduce_trace


def read(run, args):
    trace = run["trace"]
    if trace is None:
        return None
    best = None
    for dev in trace["devices"]:
        ev = reduce_trace.op_events(dev, reduce_trace.MODULES_LINE,
                                    args.get("match_all", []),
                                    args.get("match_any", []))
        if ev and (best is None or len(ev) > len(best)):
            best = ev
    if not best:
        return None
    return 1e3 * statistics.median(d for _, _, d in best)
