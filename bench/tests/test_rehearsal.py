"""The harness end to end on the CPU, through the tiny cells kept here.

The rehearsal names its platform truthfully and emits no device metric; the
default path refuses without a TPU. Adding these cells took only the files
in this directory: nothing in ``bench/run.py`` names a cell, a configuration
or a metric."""
import ast
import glob
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
    ("tiny-chat", 1), ("tiny-batch", 0), ("tiny-batch", 1),
    ("tiny-gqa-chat", 0), ("tiny-gqa-chat", 1)])
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
    if cell == "tiny-gqa-chat":
        # the family's own count, found beside the cell: 2 layers of q, o
        # (64 x 64), k, v (64 x 32), gate, up, down (64 x 128) and a head of
        # 64 x 256, two bytes each (test_window_facts.py holds its cached
        # bytes a token to the grouped-query count)
        assert result["facts"]["weight_bytes"] == 2 * (
            2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128) + 64 * 256)


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


#: a configuration's keys are its source's own, so the harness reads two:
#: the vocabulary (token ids are drawn from it) and the builder's name
GENERAL_KEYS = {"vocab_size", "builder"}


def general_sources():
    paths = [RUN, os.path.join(conftest.BENCH, "calibrate.py"),
             os.path.join(conftest.BENCH, "sweep.py")]
    for sub in ("traffic", "readers"):
        paths += sorted(glob.glob(os.path.join(conftest.BENCH, sub, "*.py")))
    return paths


def is_config(node):
    """``cfg`` / ``config``, or ``<anything>["cfg"]``."""
    if isinstance(node, ast.Name):
        return node.id in ("cfg", "config")
    return (isinstance(node, ast.Subscript)
            and isinstance(node.slice, ast.Constant)
            and node.slice.value == "cfg")


def config_keys_read(tree):
    """The constant keys that the code reads from a configuration, by
    subscript or by ``.get``."""
    keys = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript) and is_config(node.value) \
                and isinstance(node.slice, ast.Constant):
            keys.add(node.slice.value)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("get", "pop", "setdefault") \
                and is_config(node.func.value) and node.args \
                and isinstance(node.args[0], ast.Constant):
            keys.add(node.args[0].value)
    return keys


def family_imports(tree):
    """Modules of one model family imported by name: a family is reached
    through ``ctx["builder"]`` (its ``ref``, its ``work``) alone. What the
    families share (``common``) is no family's."""
    family = ("mxbench.models", "mxbench.reference", "mxbench.work")
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module in family:
            found += [f"{node.module}.{a.name}" for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith(tuple(f + "." for f in family)):
            found.append(node.module)
        elif isinstance(node, ast.Import):
            found += [a.name for a in node.names
                      if a.name.startswith(tuple(f + "." for f in family))]
    return [m for m in found if not m.endswith(".common")]


@pytest.mark.parametrize("path", general_sources(),
                         ids=lambda p: os.path.relpath(p, conftest.BENCH))
def test_general_code_reads_no_familys_configuration_key(path):
    """The promise of ``bench/README.md``: a model family is its builder, its
    reference and its count, and nothing general knows its keys."""
    tree = ast.parse(open(path).read())
    assert config_keys_read(tree) <= GENERAL_KEYS
    assert not family_imports(tree)
    gpt2_keys = {"n_embd", "n_layer", "n_head", "n_positions", "n_inner",
                 "n_ctx"}
    constants = {n.value for n in ast.walk(tree)
                 if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    assert not constants & gpt2_keys


def test_the_walk_sees_a_key_where_one_is_read():
    tree = ast.parse(
        'a = cfg["n_embd"]\nb = ctx["cfg"].get("hidden_size", 0)\n'
        'c = run["cfg"]["vocab_size"]\nfrom mxbench.reference import gpt2\n'
        'from mxbench.reference.common import seed_words')
    assert config_keys_read(tree) == {"n_embd", "hidden_size", "vocab_size"}
    assert family_imports(tree) == ["mxbench.reference.gpt2"]
