#!/usr/bin/env python
"""bench_gate: noise-aware perf-regression gate over BENCH_r*.json history.

bench.py's ``compare_vs_prev`` prints advisory deltas inside the bench
line; this tool is the GATE — it exits non-zero when the newest round
(or an uncommitted candidate line) shows a statistically significant
drop on any tracked higher-is-better metric, so a perf PR cannot land a
regression the way a test failure cannot land.

Noise model (runs vary from one to the next): every bench
round records per-trial timing stats (``_stats``: min/median/max,
``trials_s``, ``spread_pct``). A drop only counts as a regression when
it exceeds ALL of:

- ``--floor`` (default 5%) — the minimum meaningful delta;
- the candidate round's own per-trial relative spread for that metric;
- the median per-trial spread of the baseline rounds — so one lucky
  low-spread historical round cannot make normal noise trip the gate.

The baseline value is the MEDIAN of up to the last ``--window`` (3)
prior rounds, not just the previous round: one contended historical
round cannot mask (or fake) a regression.

Waivers (the mxlint-baseline pattern): a justified, committed exception
lives in ``tools/bench_gate_baseline.json`` as
``{"waivers": {"<metric>": {"justification": "...",
"through_round": N}}}`` — the metric is exempt while the candidate
round is <= ``through_round`` (``null`` = indefinitely, e.g. a metric
retired by a redesign). Stale waivers (metric passing on its own) are
reported so the file shrinks back.

Usage::

    python tools/bench_gate.py                      # gate newest committed round
    python tools/bench_gate.py --candidate out.json # gate an uncommitted line
    python tools/bench_gate.py --format json
    python tools/bench_gate.py --self-test          # gate-math unit checks

Runs WITHOUT jax: it imports bench.py only for the tracked-metric table
and spread helper (both pure python + numpy at import).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "bench_gate_baseline.json")


def _bench():
    """bench.py's tracked-metric table + spread helper (jax is only
    imported inside its bench functions, never at module import)."""
    import bench
    return bench


def load_history(directory: str) -> List[Tuple[int, Dict[str, Any]]]:
    """All committed rounds, ``[(round_number, parsed_line), ...]``
    ascending. Files hold the driver schema ``{"parsed": {...}}``
    (see bench._load_prev_round); a bare parsed line is accepted too.
    Unreadable/malformed files are skipped — the gate judges what it
    can read."""
    rounds = []
    for f in glob.glob(os.path.join(directory, "BENCH_r*.json")):
        m = re.search(r"BENCH_r0*(\d+)\.json$", f)
        if not m:
            continue
        try:
            with open(f) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed", doc) if isinstance(doc, dict) else None
        if isinstance(parsed, dict):
            rounds.append((int(m.group(1)), parsed))
    rounds.sort()
    return rounds


def load_waivers(path: Optional[str]) -> Dict[str, Dict[str, Any]]:
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    waivers = doc.get("waivers", {})
    return waivers if isinstance(waivers, dict) else {}


def _metric_spread(parsed: Dict[str, Any], metric: str) -> float:
    """Per-trial relative spread recorded alongside ``metric`` in one
    round (0.0 when the round predates spread recording)."""
    b = _bench()
    stats = parsed.get(b._METRIC_TIMING.get(metric, ""), {})
    return b._rel_spread(stats if isinstance(stats, dict) else {})


def gate(history: List[Tuple[int, Dict[str, Any]]],
         candidate: Optional[Tuple[int, Dict[str, Any]]] = None,
         floor: float = 0.05, window: int = 3,
         waivers: Optional[Dict[str, Dict[str, Any]]] = None
         ) -> Dict[str, Any]:
    """Pure gate math (the --self-test subject). ``candidate`` defaults
    to the newest history round (judged against the rounds before it).
    Returns the report; ``report["ok"]`` is the gate verdict."""
    b = _bench()
    waivers = waivers or {}
    if candidate is None:
        if len(history) < 1:
            return {"ok": True, "reason": "no bench history", "metrics": {}}
        candidate = history[-1]
        history = history[:-1]
    cand_round, cand = candidate

    metrics_report: Dict[str, Any] = {}
    regressions, waived, stale = [], [], []
    for metric in b._METRIC_TIMING:
        val = cand.get(metric)
        if not isinstance(val, (int, float)) or isinstance(val, bool):
            continue
        prior = [(r, p[metric], _metric_spread(p, metric))
                 for r, p in history
                 if isinstance(p.get(metric), (int, float))
                 and not isinstance(p.get(metric), bool)
                 and p[metric] > 0]
        if not prior:
            metrics_report[metric] = {"value": val, "status": "new"}
            continue
        recent = prior[-window:]
        base = statistics.median(v for _, v, _ in recent)
        if base <= 0:
            metrics_report[metric] = {"value": val, "status": "new"}
            continue
        delta = (val - base) / base
        tol = max(floor, _metric_spread(cand, metric),
                  statistics.median(s for _, _, s in recent))
        entry = {
            "value": val,
            "baseline": base,
            "baseline_rounds": [r for r, _, _ in recent],
            "delta": round(delta, 4),
            "tolerance": round(tol, 4),
            "status": "ok",
        }
        if delta < -tol:
            w = waivers.get(metric)
            through = w.get("through_round") if isinstance(w, dict) else None
            if w is not None and (through is None
                                  or cand_round <= int(through)):
                entry["status"] = "waived"
                entry["justification"] = \
                    w.get("justification", "") if isinstance(w, dict) else ""
                waived.append(metric)
            else:
                entry["status"] = "regression"
                regressions.append(metric)
        metrics_report[metric] = entry
    for metric in waivers:
        if metric in metrics_report \
                and metrics_report[metric]["status"] == "ok":
            stale.append(metric)
    return {
        "ok": not regressions,
        "candidate_round": cand_round,
        "baseline_rounds": [r for r, _ in history[-window:]],
        "floor": floor,
        "metrics": metrics_report,
        "regressions": regressions,
        "waived": waived,
        "stale_waivers": stale,
    }


# ---------------------------------------------------------------------------
# self-test: the gate math on synthetic histories (no bench files, no jax)
# ---------------------------------------------------------------------------

def _synth_round(tok_s: float, spread_pct: float) -> Dict[str, Any]:
    """A minimal parsed line: one tracked throughput metric + the timing
    stats carrying its recorded per-trial spread."""
    min_s = 1.0
    return {
        "gpt2_train_tokens_per_sec": tok_s,
        "gpt2_timing": {"min_s": min_s,
                        "median_s": min_s * (1 + spread_pct / 200.0),
                        "max_s": min_s * (1 + spread_pct / 100.0),
                        "trials": 5,
                        "spread_pct": spread_pct},
    }


def self_test() -> Dict[str, Any]:
    """Gate math on synthetic histories: identical data passes, an
    injected 20% regression fails, and high-spread noise does not
    false-positive. Raises AssertionError on any violation."""
    # 1. identical rounds: no regression
    hist = [(i, _synth_round(100_000.0, 2.0)) for i in range(1, 6)]
    rep = gate(hist)
    assert rep["ok"] and not rep["regressions"], \
        f"identical history tripped the gate: {rep}"

    # 2. injected 20% tok/s drop on tight (2%) spreads: must fail
    hist = [(i, _synth_round(100_000.0, 2.0)) for i in range(1, 5)]
    hist.append((5, _synth_round(80_000.0, 2.0)))
    rep = gate(hist)
    assert not rep["ok"] and \
        rep["regressions"] == ["gpt2_train_tokens_per_sec"], \
        f"20% regression NOT flagged: {rep}"

    # 3. the same 20% drop under 30% recorded per-trial spread is inside
    #    the noise band: must NOT false-positive
    hist = [(i, _synth_round(100_000.0, 30.0)) for i in range(1, 5)]
    hist.append((5, _synth_round(80_000.0, 30.0)))
    rep = gate(hist)
    assert rep["ok"], f"noisy history false-positived: {rep}"

    # 4. one lucky low-spread round in otherwise-noisy history must not
    #    make normal jitter trip (median-of-spreads, not min)
    hist = [(1, _synth_round(100_000.0, 25.0)),
            (2, _synth_round(95_000.0, 2.0)),
            (3, _synth_round(104_000.0, 25.0)),
            (4, _synth_round(91_000.0, 25.0))]
    rep = gate(hist)
    assert rep["ok"], f"single tight round false-positived: {rep}"

    # 5. waivers: the 20% regression passes when waived through this
    #    round, fails again past the waiver's horizon
    hist = [(i, _synth_round(100_000.0, 2.0)) for i in range(1, 5)]
    hist.append((5, _synth_round(80_000.0, 2.0)))
    w = {"gpt2_train_tokens_per_sec":
         {"justification": "test", "through_round": 5}}
    rep = gate(hist, waivers=w)
    assert rep["ok"] and rep["waived"] == ["gpt2_train_tokens_per_sec"], \
        f"waiver not honored: {rep}"
    w["gpt2_train_tokens_per_sec"]["through_round"] = 4
    rep = gate(hist, waivers=w)
    assert not rep["ok"], f"expired waiver still honored: {rep}"

    # 6. a brand-new metric (no history) never gates
    hist = [(1, _synth_round(100_000.0, 2.0))]
    cand = dict(_synth_round(100_000.0, 2.0))
    cand["gpt2_decode_fused_tokens_per_sec"] = 12_345.0
    rep = gate(hist, candidate=(2, cand))
    assert rep["ok"] and \
        rep["metrics"]["gpt2_decode_fused_tokens_per_sec"]["status"] == \
        "new", f"new metric mis-gated: {rep}"

    return {"ok": True, "cases": 6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_gate",
        description="noise-aware perf-regression gate over BENCH_r*.json")
    ap.add_argument("--dir", default=REPO,
                    help="directory holding BENCH_r*.json (default: repo "
                         "root)")
    ap.add_argument("--candidate", default=None,
                    help="uncommitted bench line (bench.py stdout JSON) to "
                         "gate against the committed history; default: the "
                         "newest committed round")
    ap.add_argument("--floor", type=float, default=0.05,
                    help="minimum relative drop that can ever count "
                         "(default 0.05)")
    ap.add_argument("--window", type=int, default=3,
                    help="prior rounds the baseline median spans "
                         "(default 3)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="waiver file (default "
                         "tools/bench_gate_baseline.json)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--self-test", action="store_true",
                    help="run the gate math on synthetic histories and "
                         "exit (identical passes, 20%% regression fails, "
                         "high-spread noise does not false-positive)")
    args = ap.parse_args(argv)

    if args.self_test:
        try:
            rep = self_test()
        except AssertionError as e:
            print(json.dumps({"ok": False, "error": str(e)}))
            return 1
        print(json.dumps(rep))
        return 0

    history = load_history(args.dir)
    candidate = None
    if args.candidate:
        try:
            with open(args.candidate) as f:
                cand = json.load(f)
        except (OSError, ValueError) as e:
            print(f"bench_gate: cannot read candidate: {e}",
                  file=sys.stderr)
            return 2
        if isinstance(cand, dict) and isinstance(cand.get("parsed"), dict):
            cand = cand["parsed"]
        next_round = (history[-1][0] + 1) if history else 1
        candidate = (next_round, cand)
    rep = gate(history, candidate=candidate, floor=args.floor,
               window=args.window, waivers=load_waivers(args.baseline))

    if args.format == "json":
        print(json.dumps(rep, indent=2))
    else:
        for metric, e in sorted(rep.get("metrics", {}).items()):
            if e.get("status") == "new":
                print(f"  NEW        {metric} = {e['value']}")
                continue
            print(f"  {e['status'].upper():10s} {metric}: {e['value']} vs "
                  f"median {e['baseline']:.6g} of r{e['baseline_rounds']} "
                  f"(delta {e['delta']:+.1%}, tolerance "
                  f"{e['tolerance']:.1%})")
        if rep.get("stale_waivers"):
            print(f"note: stale waivers (metric healthy — prune): "
                  f"{rep['stale_waivers']}")
        verdict = "PASS" if rep["ok"] else \
            f"FAIL ({len(rep['regressions'])} regression(s): " \
            f"{rep['regressions']})"
        print(f"bench_gate r{rep.get('candidate_round')}: {verdict}")
    return 0 if rep["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
