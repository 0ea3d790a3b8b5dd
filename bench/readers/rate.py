"""One recorded fact over another (times ``scale``): all the work of the
window over all its time, or a share in % with ``scale`` 100."""


def read(run, args):
    facts = run["facts"]
    num, den = facts.get(args["numerator"]), facts.get(args["denominator"])
    if num is None or not den:
        return None
    return args.get("scale", 1.0) * num / den
