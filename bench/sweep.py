"""The knee of an open-loop cell, found once: the same engine takes a window
at each of a few rates, one after another. Not part of a benchmark run.

    python3 bench/sweep.py --workload <cell> --seed 1 --seconds 20 \
        --rates 1,1.5,2.25,3.4,5 --out chiprun_out/sweep.jsonl \
        [--calibrate 2,3 --controls 3]

The knee is the highest rate up to which no request is refused or fails and
the queue is no deeper at the window's end than at its middle (``knee_of``).
The cell's fixed rate is four fifths of it, written into the cell's file by
hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import calibrate   # also sets up the ``mxbench`` alias, through run.py


def pct(xs, q):
    xs = [x for x in xs if np.isfinite(x)]
    return float(np.percentile(xs, q)) if xs else None


def knee_of(rows):
    """The highest rate up to which every window kept its queue: no request
    refused or failed, and the queue no deeper at the end than at the middle
    (each a mean over a tenth of the window; under one waiting request at
    the end counts as none)."""
    knee = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        mid, end = row["queue_mid_end"]
        if row["failed"] or (end > mid and end >= 1.0):
            break
        knee = row["rate_per_s"]
    return knee


def sweep(traffic, state, seed, rates, seconds, out):
    """One window at each rate on the engine of ``state``; the rows."""
    ctx = state["ctx"]
    rows = []
    for i, rate in enumerate(rates):
        ctx["seed"] = seed + i
        ctx["spec"]["traffic"]["arrivals"]["rate_per_s"] = rate
        facts = traffic.window(state, seconds)
        facts["setup_s"] = 0.0
        facts = traffic.after_window(state, facts)
        state.pop("sample", None)
        row = {"rate_per_s": rate, "attempted": facts["attempted"],
               "failed": facts["failed"],
               "queue_mid_end": facts.get("queue_depth_end_over_mid"),
               "slots_in_use_mean": facts.get("slots_in_use_mean"),
               "ttft_p50_ms": pct(facts["ttft_ms"], 50),
               "ttft_p90_ms": pct(facts["ttft_ms"], 90),
               "itl_p50_ms": pct(facts["itl_ms"], 50),
               "itl_p95_ms": pct(facts["itl_ms"], 95),
               "tokens_per_s": facts["tokens"] / facts["window_s"],
               "drain_s": facts.get("drain_s"),
               "preemptions": facts["preemptions"],
               "generator_late_max_ms": facts["generator_late_max_ms"]}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    ctx["seed"] = seed
    row = {"knee_per_s": knee_of(rows)}
    print(json.dumps(row), flush=True)
    out.write(json.dumps(row) + "\n")
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--out", required=True)
    ap.add_argument("--calibrate", default=[],
                    type=lambda s: [int(x) for x in s.split(",")],
                    help="then bench/calibrate.py's readings on --seed and "
                         "these seeds, at four fifths of the knee")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--calibrate-seconds", type=float, default=51.0)
    args = ap.parse_args()
    traffic, ctx = calibrate._ctx(args, args.seed)
    ctx["sample_engine"] = True
    state = traffic.setup(ctx)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        rows = sweep(traffic, state, args.seed, args.rates, args.seconds, out)
        if not args.calibrate:
            traffic.release(state)
            return 0
        # the readings `correct`'s limits are set from, on the same engine,
        # at four fifths of the knee just found: one set-up for both
        rate = round(0.8 * (knee_of(rows) or min(args.rates)), 2)
        ctx["spec"]["traffic"]["arrivals"]["rate_per_s"] = rate
        ctx["sample_engine"] = False
        calibrate.emit(out, kind="calibrating_at", rate_per_s=rate)
        calibrate.serve(argparse.Namespace(
            seeds=[args.seed] + args.calibrate, controls=args.controls,
            seconds=args.calibrate_seconds), out, traffic, state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
