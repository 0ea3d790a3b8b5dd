"""A decode step's share of its roofline, in %: the least time the chip
could take for the decode steps of the traced window — every weight once for
each step the trace shows, plus the live keys and values of the tokens
decoded in the window at their true lengths, over the HBM's peak (a decode
step is bound by bandwidth: at 16 rows its FLOPs take a twentieth of that) —
over the summed device time of those step programs."""
from mxbench import reduce_trace


def read(run, args):
    trace, facts, peaks = run["trace"], run["facts"], run["peaks"]
    if trace is None or not peaks or not facts.get("decode_tokens"):
        return None
    dev = max(trace["devices"], key=reduce_trace.busy_s)
    ev = reduce_trace.op_events(dev, reduce_trace.MODULES_LINE,
                                args.get("match_all", []),
                                args.get("match_any", []))
    if not ev:
        return None
    nbytes = len(ev) * facts["weight_bytes"] + facts["decode_kv_bytes"]
    least = nbytes / peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(d for _, _, d in ev)
