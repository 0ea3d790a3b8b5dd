"""A kernel's share of its roofline: the least time the chip could take for
the kernel's work in the traced window (a fact the traffic module computed
from shapes with ``bench/flops.py``) over the summed device time of the
kernel's operations in the trace, on the busiest device, in %.

``match`` lists substrings; an operation counts when its name, or one of its
text stats (the trace's ``long_name`` / ``tf_op`` / ``hlo_category`` ...),
holds every substring of ``match_all`` or any of ``match_any``. Finds nothing
-> returns nothing: never 0."""
from mxbench import reduce_trace


def read(run, args):
    trace, least = run["trace"], run["facts"].get(args["least"])
    if trace is None or least is None:
        return None
    worst = 0.0
    for dev in trace["devices"]:
        t = reduce_trace.op_seconds(dev, args.get("line", "XLA Ops"),
                                    args.get("match_all", []),
                                    args.get("match_any", []))
        worst = max(worst, t)
    if worst <= 0.0:
        return None
    return 100.0 * least / worst
