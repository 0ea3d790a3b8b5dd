"""A percentile over ALL the samples of the window. A sample that is missing
(a request that failed, was refused or never finished) is recorded as
infinity by the traffic module and so sits in the tail, where it belongs; a
percentile that lands on one is reported as the ``missing_ms`` given (a
number, because the result line carries numbers)."""
import math


def read(run, args):
    samples = run["facts"].get(args["samples"])
    if not samples:
        return None
    xs = sorted(samples)
    q = float(args["q"])
    # nearest-rank on the sorted samples, interpolating between neighbours
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(xs[hi]):
        return float(args.get("missing", 3600e3))
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
