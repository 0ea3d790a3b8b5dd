"""The harness end to end on the CPU, through the tiny cells kept here.

The rehearsal names its platform truthfully and emits no device metric; the
default path refuses without a TPU. Adding these cells took only the files
in this directory: nothing in ``bench/run.py`` names a cell, a configuration
or a metric."""
import json
import os
import subprocess
import sys

import pytest

import conftest

RUN = os.path.join(conftest.BENCH, "run.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cell(capsys, cell, trace, seed=3000000019, seconds=1.5):
    rc = conftest.run.main(["--rehearsal", "--workload", cell, "--seed",
                            str(seed), "--seconds", str(seconds),
                            "--trace", str(trace)])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("cell,trace", [
    ("tiny-train", 0), ("tiny-train", 1), ("tiny-chat", 0),
    ("tiny-chat", 1), ("tiny-batch", 0), ("tiny-batch", 1)])
def test_tiny_cell_runs_and_is_correct(capsys, cell, trace):
    rc, result, out = run_cell(capsys, cell, trace)
    assert rc == 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["backend"] == "cpu"
    bench = json.load(open(os.path.join(conftest.BENCH, "tests",
                                        "BENCHMARK.json")))
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in bench[group]}
    for name in result["metrics"]:
        assert declared[name]["source"] != "device_trace", \
            f"{name} is a device metric and this ran on a CPU"
    if not trace:
        assert "setup_s" in result["metrics"]
    # every number compared is printed beside its limit, last on stderr
    for name, c in result["compared"].items():
        assert f"compared {name}: {c['value']} limit {c['limit']}" in out.err
    assert out.err.strip().splitlines()[-1] == "correct: True"


def test_same_seed_same_inputs():
    from mxbench.traffic import serve_common as sc
    tr = json.load(open(os.path.join(
        conftest.BENCH, "tests", "workloads", "tiny-chat.json")))["traffic"]
    cfg = {"vocab_size": 256}
    a = sc.make_requests(tr, cfg, 4000000001, 20)
    b = sc.make_requests(tr, cfg, 4000000001, 20)
    c = sc.make_requests(tr, cfg, 4000000002, 20)
    assert all((x["prompt"] == y["prompt"]).all() and x["seed"] == y["seed"]
               for x, y in zip(a, b))
    # another seed: the same timeline of sizes, other tokens
    assert [len(x["prompt"]) for x in a] == [len(x["prompt"]) for x in c]
    assert [x["max_new"] for x in a] == [x["max_new"] for x in c]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, c))
    d = sc.arrival_offsets(tr, 20, 10.0)
    assert (d == sc.arrival_offsets(tr, 20, 10.0)).all()
    assert 0 < d[0] and d[-1] < 10.0 and (d[1:] > d[:-1]).all()


def test_default_path_refuses_without_a_tpu():
    for cell in ("gpt2m-train-b8", "tiny-train"):
        p = subprocess.run(
            [sys.executable, RUN, "--workload", cell, "--seed", "1",
             "--seconds", "1", "--trace", "0"], env=ENV,
            capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert "refused" in p.stderr
        assert '"correct"' not in p.stdout


def test_rehearsal_refuses_a_real_cell():
    p = subprocess.run(
        [sys.executable, RUN, "--rehearsal", "--workload", "gpt2m-train-b8",
         "--seed", "1", "--seconds", "1", "--trace", "0"], env=ENV,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_nothing_in_run_py_names_a_cell_config_or_metric():
    text = open(RUN).read()
    names = set()
    for path in (os.path.join(conftest.ROOT, "BENCHMARK.json"),
                 os.path.join(conftest.BENCH, "tests", "BENCHMARK.json")):
        b = json.load(open(path))
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names |= {e["name"] for e in b[group]}
    names.discard("setup_s")   # the harness itself measures the set-up time
    assert not [n for n in names if n in text]
