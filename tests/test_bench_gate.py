"""bench.py compare_vs_prev hardening + the tools/bench_gate.py gate.

Pure-python tier-1 coverage (no jax touched beyond the package import
the test runner already paid): the advisory tripwire must survive
missing/zero/new-key inputs without KeyErrors, and the exit-status gate
must pass identical histories, fail an injected 20% regression, ignore
high-spread noise, and honor/expire waivers — a five-round history
loaded from disk must gate clean."""
import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _bench():
    return _load("_t_bench", os.path.join(REPO, "bench.py"))


def _gate():
    return _load("_t_bench_gate",
                 os.path.join(REPO, "tools", "bench_gate.py"))


# ---------------------------------------------------------------- bench.py
def test_compare_vs_prev_flags_real_regression():
    b = _bench()
    line = {"gpt2_train_tokens_per_sec": 80_000.0,
            "gpt2_timing": {"min_s": 1.0, "max_s": 1.02}}
    prev = {"gpt2_train_tokens_per_sec": 100_000.0,
            "gpt2_timing": {"min_s": 1.0, "max_s": 1.02}}
    deltas, regressions = b.compare_vs_prev(line, prev)
    assert deltas["gpt2_train_tokens_per_sec"] == -0.2
    assert regressions == ["gpt2_train_tokens_per_sec"]


def test_compare_vs_prev_spread_masks_noise():
    b = _bench()
    line = {"gpt2_train_tokens_per_sec": 80_000.0,
            "gpt2_timing": {"min_s": 1.0, "max_s": 1.3}}  # 30% spread
    prev = {"gpt2_train_tokens_per_sec": 100_000.0,
            "gpt2_timing": {"min_s": 1.0, "max_s": 1.02}}
    _, regressions = b.compare_vs_prev(line, prev)
    assert regressions == []


def test_compare_vs_prev_handles_malformed_inputs():
    """Missing prev, non-dict prev, new metrics, retired metrics, bool/
    string values, zero-spread and malformed timing dicts: no KeyError,
    no ZeroDivisionError, clean skips (the satellite contract)."""
    b = _bench()
    line = {
        "gpt2_train_tokens_per_sec": 90_000.0,
        "gpt2_timing": {"min_s": 0.0, "max_s": 0.0},   # zero-spread
        "gpt2_decode_fused_tokens_per_sec": 15_000.0,  # new this round
        "gpt2_decode_fused_timing": "not-a-dict",
        "aot_warmstart_speedup": True,                 # bool is not a value
    }
    prev = {
        "gpt2_train_tokens_per_sec": 100_000.0,
        # no timing recorded at all in the previous round
        "gpt2_decode_int8_tokens_per_sec": 7_000.0,    # retired this round
        "pipeline_input_bound_speedup": "1.8",         # stringly-typed
    }
    deltas, regressions = b.compare_vs_prev(line, prev)
    assert deltas == {"gpt2_train_tokens_per_sec": -0.1}
    assert regressions == ["gpt2_train_tokens_per_sec"]
    # non-dict / empty prev: total no-op
    assert b.compare_vs_prev(line, None) == ({}, [])
    assert b.compare_vs_prev(line, {}) == ({}, [])
    # zero/negative prev values cannot divide
    assert b.compare_vs_prev(
        {"gpt2_train_tokens_per_sec": 1.0},
        {"gpt2_train_tokens_per_sec": 0.0}) == ({}, [])


def test_rel_spread_total():
    b = _bench()
    assert b._rel_spread({"min_s": 1.0, "max_s": 1.5}) == 0.5
    assert b._rel_spread({"min_s": 0.0, "max_s": 1.0}) == 0.0
    assert b._rel_spread({}) == 0.0
    assert b._rel_spread(None) == 0.0
    assert b._rel_spread({"min_s": "x", "max_s": 1.0}) == 0.0


# ------------------------------------------------------------- bench_gate
def test_gate_self_test_passes():
    g = _gate()
    assert g.self_test() == {"ok": True, "cases": 6}


def _write_history(directory, rounds=5):
    """A synthetic five-round BENCH_rNN.json history in the driver's
    schema (``{"parsed": {...}}``): steady throughput with a few percent
    of round-to-round wobble and a recorded per-trial spread."""
    for r in range(1, rounds + 1):
        wobble = 1.0 + 0.01 * ((r * 7) % 5 - 2)
        dt = 0.5 / wobble
        parsed = {
            "gpt2_train_tokens_per_sec": 100_000.0 * wobble,
            "gpt2_train_timing": {
                "min_s": dt, "median_s": dt * 1.01, "max_s": dt * 1.03,
                "trials": 5, "spread_pct": 3.0},
            "gpt2_decode_tokens_per_sec": 8_000.0 * wobble,
            "gpt2_decode_timing": {
                "min_s": 0.128 / wobble, "median_s": 0.13 / wobble,
                "max_s": 0.134 / wobble, "trials": 6, "spread_pct": 4.7},
        }
        with open(os.path.join(directory, f"BENCH_r{r:02d}.json"),
                  "w") as f:
            json.dump({"parsed": parsed}, f)


def test_gate_passes_committed_history(tmp_path):
    """A five-round history read back from disk must gate clean with the
    committed (empty) waiver file — the guard that keeps the gate
    landable in CI."""
    g = _gate()
    _write_history(str(tmp_path))
    history = g.load_history(str(tmp_path))
    assert len(history) >= 5, "bench history missing"
    rep = g.gate(history, waivers=g.load_waivers(g.DEFAULT_BASELINE))
    assert rep["ok"], f"history fails its own gate: {rep}"


def test_gate_fails_synthetic_regression_on_history(tmp_path):
    """A 20% tok/s drop against a five-round history must fail the gate
    (exercises the loader end to end, still jax-free)."""
    g = _gate()
    _write_history(str(tmp_path))
    history = g.load_history(str(tmp_path))
    cand = dict(history[-1][1])
    cand["gpt2_train_tokens_per_sec"] = \
        cand["gpt2_train_tokens_per_sec"] * 0.8
    rep = g.gate(history, candidate=(history[-1][0] + 1, cand),
                 waivers=g.load_waivers(g.DEFAULT_BASELINE))
    assert not rep["ok"]
    assert "gpt2_train_tokens_per_sec" in rep["regressions"]


def test_gate_cli_self_test_without_jax():
    """`bench_gate.py --self-test` must run in an interpreter where jax
    is unimportable (the no-jax tier-1 contract for the gate tool).
    ``-S`` skips site initialisation (nothing may pre-import jax);
    site-packages comes back via PYTHONPATH (numpy stays importable),
    and jax is poisoned for good measure."""
    import numpy
    sitepkgs = os.path.dirname(os.path.dirname(numpy.__file__))
    tool = os.path.abspath(os.path.join(REPO, "tools", "bench_gate.py"))
    code = (
        "import sys; sys.modules['jax'] = None; "
        "sys.argv = ['bench_gate', '--self-test']; "
        f"import runpy; runpy.run_path({tool!r}, run_name='__main__')"
    )
    env = dict(os.environ, PYTHONPATH=sitepkgs)
    out = subprocess.run([sys.executable, "-S", "-c", code],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    # runpy propagates main()'s SystemExit(0) as returncode 0
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]


def test_gate_stale_waiver_reported(tmp_path):
    g = _gate()
    hist = [(i, g._synth_round(100_000.0, 2.0)) for i in range(1, 6)]
    w = {"gpt2_train_tokens_per_sec":
         {"justification": "old exception", "through_round": 99}}
    rep = g.gate(hist, waivers=w)
    assert rep["ok"]
    assert rep["stale_waivers"] == ["gpt2_train_tokens_per_sec"]
