"""A number the traffic module recorded, as it stands (times ``scale``)."""


def read(run, args):
    value = run["facts"].get(args["key"])
    if value is None:
        return None
    return value * args.get("scale", 1.0)
