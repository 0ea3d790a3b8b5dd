"""The whole window's share of the chips' peak: the operations the model
needs for the tokens of the window (counted from shapes by ``bench/flops.py``,
recomputation not counted) over window x chips x peak bf16 FLOP/s, in %."""


def read(run, args):
    facts, peaks = run["facts"], run["peaks"]
    flops = facts.get(args["flops"])
    if flops is None or not peaks or not facts.get("window_s"):
        return None
    return 100.0 * flops / (facts["window_s"] * run["chips"]
                            * peaks["bf16_flops_per_s"])
