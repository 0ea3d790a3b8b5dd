"""Static analysis + runtime guards (mxnet_tpu/analysis): every mxlint
rule fires on a seeded fixture and stays quiet on clean code, the
tools/mxlint.py gate passes over mxnet_tpu/ with zero unbaselined
findings, and the runtime guards (no_sync / no_recompile / alias
sentinel / lock-order witness) each catch a deliberately injected
hazard — including the PR-4 staging-buffer corruption class at dispatch
time."""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, metrics, np
from mxnet_tpu.analysis import guards, linter
from mxnet_tpu.gluon import nn
from mxnet_tpu.models import GPTModel
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.pipeline import DevicePrefetcher
from mxnet_tpu.serve import InferenceEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, select=None):
    findings, _edges = linter.lint_source(textwrap.dedent(src),
                                          "fixture.py", select=select)
    return findings


def _rules(findings):
    return sorted({f.rule for f in findings})


@pytest.fixture
def debug_guards():
    guards.enable_debug()
    guards.reset_lock_witness()
    yield guards
    guards.disable_debug()
    guards.reset_lock_witness()


@pytest.fixture(scope="module")
def gpt_model():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=32, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    return net


# =========================================================== linter rules
def test_mx001_sync_in_traced_fn():
    findings = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            y = float(x)
            h = np.asarray(x)
            x.block_until_ready()
            v = x.item()
            return x
    """)
    assert _rules(findings) == ["MX001"]
    assert len(findings) == 4


def test_mx001_sync_in_hot_loop():
    findings = _lint("""
        import jax
        step = jax.jit(lambda x: x + 1)

        def train(batches):
            out = []
            for b in batches:
                r = step(b)
                out.append(r.item())
        """)
    assert _rules(findings) == ["MX001"]
    assert "hot loop" in findings[0].message


def test_mx001_negative_eager_sync_ok():
    findings = _lint("""
        import numpy as np

        def eager(x):
            v = float(x)
            a = np.asarray(x)
            return x.item() + v
    """)
    assert findings == []


def test_mx002_jit_in_loop_and_unhashable_static():
    findings = _lint("""
        import jax

        def rebuild(fs, xs):
            for f in fs:
                g = jax.jit(f)
                g(xs)

        h = jax.jit(lambda x, cfg: x, static_argnums=(1,))

        def call(x):
            return h(x, [1, 2, 3])
    """)
    assert _rules(findings) == ["MX002"]
    assert len(findings) == 2


def test_mx002_negative_stable_jit():
    findings = _lint("""
        import jax

        h = jax.jit(lambda x, n: x, static_argnums=(1,))

        def call(x):
            g = jax.jit(lambda y: y)
            return h(x, 4) + g(x)
    """)
    assert findings == []


def test_mx003_tracer_leaks():
    findings = _lint("""
        import jax

        class M:
            @jax.jit
            def fwd(self, x):
                self.cache = x
                return x

        def outer(xs):
            acc = []

            def body(c, x):
                acc.append(x)
                return c, x

            return jax.lax.scan(body, 0, xs)

        @jax.jit
        def g(x):
            global state
            state = x
            return x
    """)
    assert _rules(findings) == ["MX003"]
    assert len(findings) == 3


def test_mx003_negative_local_mutation_ok():
    findings = _lint("""
        import jax

        @jax.jit
        def f(x):
            parts = []
            parts.append(x)
            table = {}
            table["x"] = x
            return parts, table
    """)
    assert findings == []


def test_mx004_alias_hazard_and_copy_negative():
    findings = _lint("""
        import numpy as np

        class Engine:
            def __init__(self, fn):
                self.buf = np.zeros(8, np.int32)
                self.safe = np.zeros(8, np.int32)
                self.fn = fn

            def dispatch(self):
                self.fn(self.buf[:4])
                self.fn(self.safe[:4].copy())

            def advance(self):
                self.buf[0] = 1
                self.safe[0] = 1
    """)
    assert _rules(findings) == ["MX004"]
    assert len(findings) == 1
    assert "self.buf" in findings[0].message


def test_mx004_negative_immutable_buffer():
    # never mutated -> no hazard even without .copy()
    findings = _lint("""
        import numpy as np

        class Engine:
            def __init__(self, fn):
                self.buf = np.zeros(8, np.int32)
                self.fn = fn

            def dispatch(self):
                self.fn(self.buf[:4])
    """)
    assert findings == []


def test_mx005_blocking_under_lock():
    findings = _lint("""
        import json
        import threading
        import time

        class W:
            def __init__(self):
                self.lock = threading.Lock()

            def bad(self):
                with self.lock:
                    with open("f", "w") as f:
                        json.dump({}, f)
                    time.sleep(1)

            def writer(self):
                with open("g", "w") as f:
                    f.write("x")

            def bad_indirect(self):
                with self.lock:
                    self.writer()

            def ok(self):
                with self.lock:
                    x = 1 + 2
                return x
    """)
    assert _rules(findings) == ["MX005"]
    assert len(findings) == 4        # open, json.dump, sleep, self.writer()


def test_mx005_self_deadlock_and_cond_wait_ok():
    findings = _lint("""
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition(self._lock)

            def deadlock(self):
                with self._lock:
                    with self._lock:
                        pass

            def fine(self):
                with self._cond:
                    self._cond.wait(0.1)
    """)
    assert len(findings) == 1
    assert "re-acquiring" in findings[0].message


def test_mx005_lock_order_cycle(tmp_path):
    src = textwrap.dedent("""
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def forward():
            with lock_a:
                with lock_b:
                    pass

        def backward():
            with lock_b:
                with lock_a:
                    pass
    """)
    p = tmp_path / "order.py"
    p.write_text(src)
    findings = linter.lint_paths([str(p)])
    cycle = [f for f in findings if "cycle" in f.message]
    assert cycle, findings
    assert all(f.rule == "MX005" for f in cycle)


def test_lock_order_cycle_edges_suppressible_and_distinct(tmp_path):
    """Each cycle edge fingerprints independently (snippet = the edge),
    and an MX005 suppression at an acquisition site removes that edge
    from the order graph entirely."""
    body = """
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def forward():
            with lock_a:
                with lock_b:
                    pass

        def backward():
            with lock_b:
                with lock_a:{SUPPRESS}
                    pass
    """
    p = tmp_path / "order2.py"
    p.write_text(textwrap.dedent(body).replace("{SUPPRESS}", ""))
    cycle = [f for f in linter.lint_paths([str(p)]) if "cycle" in f.message]
    assert len(cycle) == 2
    assert len({f.fingerprint for f in cycle}) == 2     # per-edge identity
    assert {f.snippet for f in cycle} == {"lock_a -> lock_b",
                                          "lock_b -> lock_a"}
    p.write_text(textwrap.dedent(body).replace(
        "{SUPPRESS}", "   # mxlint: disable=MX005 -- justified inversion"))
    assert [f for f in linter.lint_paths([str(p)])
            if "cycle" in f.message] == []


def test_linter_loads_lazily():
    """Runtime subsystems import mxnet_tpu.analysis for guards only; the
    AST linter module must not load with them (PEP 562 lazy attr)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; import mxnet_tpu.analysis.guards; "
         "assert 'mxnet_tpu.analysis.linter' not in sys.modules, 'eager'; "
         "from mxnet_tpu.analysis import lint_source; "
         "assert 'mxnet_tpu.analysis.linter' in sys.modules; print('ok')"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0 and "ok" in proc.stdout, \
        proc.stdout + proc.stderr


def test_checkpoint_keep_best_concurrent_saves(tmp_path, debug_guards):
    """Racing keep_best saves must neither crash on the symlink swap nor
    leave 'best' pointing at a checkpoint worse than the recorded best."""
    mgr = mx.checkpoint.CheckpointManager(
        str(tmp_path), period=1, keep_last=0, keep_best=True,
        extra_state=lambda: {})
    errors = []

    def saver(i):
        try:
            mgr._write_local(i, float(10 - i), None,
                             {"seed_state": None})
        except Exception as e:            # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=saver, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    best = os.path.join(str(tmp_path), "best")
    assert os.path.islink(best)
    target_step = int(os.readlink(best).split("-")[1])
    assert float(10 - target_step) == mgr._best
    guards.check_lock_order()


def test_suppressions_and_fingerprints():
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x)   # mxlint: disable=MX001 -- deliberate fixture
    """
    assert _lint(src) == []
    # comment-above form
    src2 = """
        import jax

        @jax.jit
        def f(x):
            # mxlint: disable=MX001 -- deliberate, long justification
            # spanning two comment lines
            return float(x)
    """
    assert _lint(src2) == []
    # fingerprints survive line drift (same content, different line)
    f1 = _lint("import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n")
    f2 = _lint("import jax\n# moved\n\n\n@jax.jit\ndef f(x):\n"
               "    return float(x)\n")
    assert f1 and f2
    assert f1[0].fingerprint == f2[0].fingerprint
    assert f1[0].line != f2[0].line


def test_skip_file_pragma():
    assert _lint("""
        # mxlint: skip-file
        import jax

        @jax.jit
        def f(x):
            return float(x)
    """) == []


# ======================================================== the tier-1 gate
def test_mxlint_gate_over_mxnet_tpu():
    """tools/mxlint.py over the real tree must exit 0: every finding is
    fixed or carries an inline justification / baseline entry."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "mxnet_tpu", "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert doc["new"] == []


def test_mxlint_cli_fails_on_new_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
    """))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         str(bad), "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["findings"][0]["rule"] == "MX001"
    assert doc["new"]
    # baselining the finding turns the gate green without touching code
    baseline = tmp_path / "baseline.json"
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         str(bad), "--baseline", str(baseline), "--write-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=60, check=True)
    proc2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         str(bad), "--baseline", str(baseline)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc2.returncode == 0, proc2.stdout


def test_mxlint_cli_rejects_bad_invocations(tmp_path):
    tool = os.path.join(REPO, "tools", "mxlint.py")
    # typo'd path must not leave the gate silently green
    proc = subprocess.run([sys.executable, tool, "no/such/dir"],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 2
    assert "no such file" in proc.stderr.lower()
    # rule-filtered baseline rewrite would drop other rules' entries
    proc2 = subprocess.run(
        [sys.executable, tool, "mxnet_tpu", "--select", "MX005",
         "--write-baseline", "--baseline", str(tmp_path / "b.json")],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc2.returncode == 2
    assert "--select" in proc2.stderr


# ============================================== Pallas kernel rules (MX1xx)

_PL_PRELUDE = """
    import jax, jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
"""

_MX101_MISSING_WAIT = _PL_PRELUDE + """
    def _kern(x_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(x_ref, buf, sem)
        cp.start()
        o_ref[...] = buf[...]

    def run(x):
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
            grid=(1,),
        )(x)
"""

_MX101_DOUBLE_START = _PL_PRELUDE + """
    def _kern(x_ref, o_ref, buf, sem):
        pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).start()
        pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).start()
        pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).wait()
        pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).wait()
        o_ref[...] = buf[0]

    def run(x):
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, 8, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
            grid=(1,),
        )(x)
"""

# the double-buffer rotation idiom of the shipped DMA kernel, condensed:
# warm depth slots, then wait slot j%depth before prefetching j+depth
# into the slot the wait just freed
_MX101_ROTATION_OK = _PL_PRELUDE + """
    def _kern(x_ref, o_ref, buf, sem, acc):
        n = 8
        depth = 2

        def start(j):
            pltpu.make_async_copy(x_ref.at[j], buf.at[j % depth],
                                  sem.at[j % depth]).start()

        def warm(j, c):
            start(j)
            return c

        lax.fori_loop(0, depth, warm, 0)

        def body(j, c):
            pltpu.make_async_copy(x_ref.at[j], buf.at[j % depth],
                                  sem.at[j % depth]).wait()

            @pl.when(j + depth < n)
            def _prefetch():
                start(j + depth)

            return c + buf[j % depth, 0, 0]

        acc[0] = lax.fori_loop(0, n, body, 0.0)
        o_ref[...] = acc[...]

    def run(x):
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((1,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, 8, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((1,), jnp.float32)],
            grid=(1,),
        )(x)
"""

_MX102_DIRECT_LOAD = _PL_PRELUDE + """
    def _kern(hbm_ref, o_ref):
        o_ref[...] = hbm_ref[0]

    def run(x):
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            grid=(1,),
        )(x)
"""

# gate convention of the shipped fusable_* family: last statement
# compares a byte sum against a knob call
_MX103_TEMPLATE = _PL_PRELUDE + """
    def _budget():
        return 1 << 20

    def gate_ok(B, D):
        need = {NEED}
        return need <= _budget()

    def _kern(x_ref, o_ref, buf):
        o_ref[...] = x_ref[...] + buf[...]

    def run(x):
        B, D = x.shape
        use = gate_ok(B, D)
        if use:
            return pl.pallas_call(
                _kern,
                in_specs=[pl.BlockSpec((B, D), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((B, D), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
                scratch_shapes=[pltpu.VMEM((B, 2 * D), jnp.float32)],
                grid=(1,),
            )(x)
        return x
"""


def _kanalyze(src, path="kfix.py"):
    from mxnet_tpu.analysis import kernels
    return kernels.analyze_source(textwrap.dedent(src), path=path)


def test_mx101_missing_wait_flagged_and_fixed_clean():
    rep = _kanalyze(_MX101_MISSING_WAIT)
    assert [f["rule"] for f in rep.findings] == ["MX101"]
    assert "never waited" in rep.findings[0]["message"]
    fixed = _MX101_MISSING_WAIT.replace(
        "o_ref[...] = buf[...]", "cp.wait()\n        o_ref[...] = buf[...]")
    assert _kanalyze(fixed).findings == []


def test_mx101_double_start_flagged_distinct_slots_clean():
    rep = _kanalyze(_MX101_DOUBLE_START)
    assert [f["rule"] for f in rep.findings] == ["MX101"]
    assert "re-started into slot" in rep.findings[0]["message"]
    # same sequence into DISTINCT slots is the legal ping-pong
    distinct = _MX101_DOUBLE_START.replace(
        "pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).start()\n"
        "        pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).start()",
        "pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).start()\n"
        "        pltpu.make_async_copy(x_ref, buf.at[1], sem.at[1]).start()",
        ).replace(
        "pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).wait()\n"
        "        pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).wait()",
        "pltpu.make_async_copy(x_ref, buf.at[0], sem.at[0]).wait()\n"
        "        pltpu.make_async_copy(x_ref, buf.at[1], sem.at[1]).wait()")
    assert _kanalyze(distinct).findings == []


def test_mx101_rotation_proof():
    # the shipped double-buffer idiom is provably safe
    assert _kanalyze(_MX101_ROTATION_OK).findings == []
    # prefetch distance depth+1 overwrites a copy still in flight
    skew = _MX101_ROTATION_OK.replace(
        "start(j + depth)", "start(j + depth + 1)").replace(
        "j + depth < n", "j + depth + 1 < n")
    rep = _kanalyze(skew)
    assert [f["rule"] for f in rep.findings] == ["MX101"]
    assert "rotation" in rep.findings[0]["message"]


def test_mx102_any_ref_use():
    rep = _kanalyze(_MX102_DIRECT_LOAD)
    assert [f["rule"] for f in rep.findings] == ["MX102"]
    assert "pl.ANY" in rep.findings[0]["message"]
    # feeding copies only (the legal use) is clean — MISSING_WAIT's
    # fixed variant already covers an ANY ref used solely as a DMA source


def test_mx103_gate_mismatch_and_agreement():
    bad = _MX103_TEMPLATE.replace("{NEED}", "B * D * 4")
    rep = _kanalyze(bad)
    assert [f["rule"] for f in rep.findings] == ["MX103"]
    assert [(p.gate, p.agree) for p in rep.pairs] == [("gate_ok", False)]
    ok = _MX103_TEMPLATE.replace("{NEED}", "B * 2 * D * 4")
    rep2 = _kanalyze(ok)
    assert rep2.findings == []
    assert [(p.gate, p.agree) for p in rep2.pairs] == [("gate_ok", True)]


def test_kernel_corpus_clean():
    """Zero unsuppressed MX1xx findings (and zero analyzer notes) over
    the whole shipped kernel family."""
    from mxnet_tpu.analysis import kernels
    sites = 0
    for fn in ("fused_block_gemv.py", "attention.py", "int8_gemv.py"):
        rep = kernels.analyze_file(
            os.path.join(REPO, "mxnet_tpu", "ops", fn))
        assert rep.findings == [], (fn, rep.findings)
        assert rep.notes == [], (fn, rep.notes)
        sites += len(rep.kernels)
    assert sites == 7    # the family: fused head + 4 attention + 2 gemv


def test_kernel_rules_flow_through_linter():
    """MX1xx findings ride the normal mxlint pipeline: Finding objects
    with fingerprints, inline suppressions, --select filtering."""
    findings = _lint(_MX101_MISSING_WAIT)
    assert [f.rule for f in findings] == ["MX101"]
    assert findings[0].fingerprint
    suppressed = _MX101_MISSING_WAIT.replace(
        "cp.start()",
        "cp.start()  # mxlint: disable=MX101 -- fixture justification")
    assert _lint(suppressed) == []
    assert _lint(_MX101_MISSING_WAIT, select=["MX102"]) == []


def test_mxlint_cli_kernels_selector():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "mxnet_tpu/ops", "--kernels", "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    reports = {r["path"]: r for r in doc["kernel_reports"]}
    gemv = reports["mxnet_tpu/ops/fused_block_gemv.py"]
    assert len(gemv["kernels"]) == 1          # the fused head
    assert gemv["pairs"] == []                # no VMEM gate ships today


def test_mxlint_cli_jax_free():
    """tools/mxlint.py (MX1xx and --metrics included) must work where
    jax cannot import."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "import importlib.util, os\n"
        "spec = importlib.util.spec_from_file_location('mxlint', "
        "os.path.join(%r, 'tools', 'mxlint.py'))\n"
        "mx = importlib.util.module_from_spec(spec)\n"
        "sys.modules['mxlint'] = mx\n"
        "spec.loader.exec_module(mx)\n"
        "assert mx.main(['mxnet_tpu/ops', '--kernels']) == 0\n"
        "assert mx.main(['--metrics']) == 0\n"
        "print('ok')\n" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "ok" in proc.stdout, \
        proc.stdout + proc.stderr


# ===================================== telemetry contract (mxlint --metrics)


def test_metrics_contract_token_grammar():
    from mxnet_tpu.analysis import metrics_contract as mc
    # label braces strip; alternation braces and slashes expand
    assert mc._expand("mxnet_foo_total{op}") == (["mxnet_foo_total"], False)
    assert mc._expand("mxnet_a_{x,y}_total")[0] == [
        "mxnet_a_x_total", "mxnet_a_y_total"]
    assert mc._expand("mxnet_spec_drafted/accepted/rejected_tokens_total"
                      )[0] == ["mxnet_spec_drafted_tokens_total",
                               "mxnet_spec_accepted_tokens_total",
                               "mxnet_spec_rejected_tokens_total"]
    assert mc._expand("mxnet_serve_*") == (["mxnet_serve_"], True)
    # nested label brace inside an expansion group
    assert mc._expand("mxnet_g_{hits{tier=a|b},misses}_total")[0] == [
        "mxnet_g_hits_total", "mxnet_g_misses_total"]


def test_metrics_contract_readme_parsing():
    from mxnet_tpu.analysis import metrics_contract as mc
    text = textwrap.dedent("""
        Some prose with `mxnet_one_total{op}` and a fence:
        ```python
        x = 1  # `mxnet_not_a_doc_total` inside a fence does not count
        ```
        Catalog below. Metrics catalog (all `mxnet_*`):

        | Metric | Kind |
        |---|---|
        | `two_total{op}` / `three_seconds` | counter |

        Wrapped span: `mxnet_wrapped_{a,
        b}_total` done.
    """)
    exact, prefixes = mc.documented_tokens(text)
    assert "mxnet_one_total" in exact
    assert "mxnet_two_total" in exact and "mxnet_three_seconds" in exact
    assert "mxnet_wrapped_a_total" in exact and "mxnet_wrapped_b_total" \
        in exact
    assert "mxnet_not_a_doc_total" not in exact
    assert prefixes == set()    # bare mxnet_* is vacuous, dropped


def test_metrics_contract_drift_fixture(tmp_path):
    """Undocumented registration and orphaned doc/check names all trip
    the contract; a consistent fixture passes."""
    from mxnet_tpu.analysis import metrics_contract as mc
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(textwrap.dedent("""
        from x import Counter, Gauge
        A = Counter("mxnet_documented_total", "d")
        B = Gauge("mxnet_missing_from_docs", "d")
    """))
    tools = tmp_path / "tools"
    tools.mkdir()
    (tools / "metrics_check.py").write_text(
        'REQUIRED = ("mxnet_documented_total", "mxnet_ghost_total")\n')
    (tmp_path / "README.md").write_text(
        "`mxnet_documented_total{op}` and `mxnet_gone_gauge` exist.\n")
    doc = mc.check_metrics_contract(str(tmp_path))
    assert not doc["ok"]
    assert [u["name"] for u in doc["undocumented"]] == [
        "mxnet_missing_from_docs"]
    assert doc["orphaned_doc"] == ["mxnet_gone_gauge"]
    assert doc["orphaned_check"] == ["mxnet_ghost_total"]
    # fix all three legs -> green
    (pkg / "m.py").write_text(textwrap.dedent("""
        from x import Counter
        A = Counter("mxnet_documented_total", "d")
    """))
    (tools / "metrics_check.py").write_text(
        'REQUIRED = ("mxnet_documented_total",)\n')
    (tmp_path / "README.md").write_text("`mxnet_documented_total{op}`.\n")
    assert mc.check_metrics_contract(str(tmp_path))["ok"]


def test_metrics_contract_real_repo_green():
    """The committed contract holds: every registered family documented,
    no orphaned doc/check names (the tier-1 face of --metrics)."""
    from mxnet_tpu.analysis import metrics_contract as mc
    doc = mc.check_metrics_contract(REPO)
    assert doc["ok"], {
        "undocumented": doc["undocumented"],
        "orphaned_doc": doc["orphaned_doc"],
        "orphaned_check": doc["orphaned_check"]}


# ============================================= DMA ledger runtime backstop


@pytest.fixture
def fresh_metrics():
    was = metrics.enabled()
    metrics.enable()
    metrics.reset()
    yield metrics
    metrics.reset()
    if not was:
        metrics.disable()


# ========================================================= runtime guards
def test_no_sync_guard_raises_and_counts():
    x = np.ones((2, 2))
    with pytest.raises(guards.HostSyncError, match="no_sync"):
        with guards.no_sync():
            x.asnumpy()
    was = metrics.enabled()
    metrics.enable()
    try:
        before = metrics.get_sample_value("mxnet_guard_violations_total",
                                          {"guard": "no_sync"}) or 0
        with guards.no_sync(action="count") as st:
            x.asnumpy()
            x.wait_to_read()
        assert st.violations == 2
        after = metrics.get_sample_value("mxnet_guard_violations_total",
                                         {"guard": "no_sync"})
        assert after == before + 2
    finally:
        if not was:
            metrics.disable()
    # outside the window the funnel is untouched
    onp.testing.assert_array_equal(x.asnumpy(), onp.ones((2, 2)))


def test_no_sync_is_thread_local():
    x = np.ones(4)
    errs = []

    def other():
        try:
            x.asnumpy()            # no guard on THIS thread
        except Exception as e:     # noqa: BLE001
            errs.append(e)

    with guards.no_sync():
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert errs == []


def test_no_recompile_guard_catches_retrace():
    mx.random.seed(0)
    net = nn.Dense(4, in_units=4)
    net.initialize()
    net.hybridize()
    net(np.ones((2, 4))).wait_to_read()          # initial compile
    with guards.no_recompile(block="Dense"):
        net(np.ones((2, 4))).wait_to_read()      # cache hit: clean
    with pytest.raises(guards.RecompileError, match="no_recompile"):
        with guards.no_recompile(block="Dense"):
            net(np.ones((6, 4))).wait_to_read()  # new shape: retrace
    # count mode reports without raising, and the telemetry lands even
    # when the guard itself enabled metrics collection
    was = metrics.enabled()
    metrics.disable()
    try:
        before = metrics.get_sample_value(
            "mxnet_guard_violations_total", {"guard": "no_recompile"}) or 0
        with guards.no_recompile(block="Dense", action="count") as st:
            net(np.ones((7, 4))).wait_to_read()
        assert st.violations == 1
        assert metrics.get_sample_value(
            "mxnet_guard_violations_total",
            {"guard": "no_recompile"}) == before + 1
    finally:
        if was:
            metrics.enable()


def test_no_recompile_does_not_mask_body_exception():
    """A failure inside the guarded window must surface as ITSELF even
    when a retrace also happened."""
    mx.random.seed(1)
    net = nn.Dense(3, in_units=3)
    net.initialize()
    net.hybridize()
    net(np.ones((2, 3))).wait_to_read()
    with pytest.raises(RuntimeError, match="real failure"):
        with guards.no_recompile(block="Dense"):
            net(np.ones((5, 3))).wait_to_read()   # retrace happens...
            raise RuntimeError("real failure")    # ...but this wins


def test_alias_sentinel_seals_and_releases():
    buf = onp.zeros(8, onp.float32)
    sent = guards.AliasSentinel()
    with sent.inflight(buf):
        with pytest.raises(ValueError):
            buf[0] = 1.0
    buf[0] = 2.0                                  # writable again
    # nested trees + NDArray wrappers walk to numpy leaves
    tree = {"a": [onp.ones(2)], "b": (onp.ones(3),)}
    n = sent.seal(tree)
    assert n == 2
    with pytest.raises(ValueError):
        tree["a"][0][0] = 5
    sent.release_all()
    tree["a"][0][0] = 5


def test_prefetcher_alias_sentinel_catches_buffer_reuse(debug_guards):
    """A producer that reuses its yielded buffer (the PR-4 hazard class)
    must fail at its next write, surfaced at the consumer."""
    buf = onp.zeros((2, 2), onp.float32)

    def reusing_producer():
        for i in range(4):
            buf[:] = i                    # mutates the PREVIOUS yield
            yield buf

    it = DevicePrefetcher(reusing_producer(), depth=2)
    with pytest.raises(ValueError, match="read-only"):
        for _ in it:
            pass
    it.close()
    buf[:] = 9                            # released after close


def test_prefetcher_clean_producer_unaffected(debug_guards):
    def fresh_producer():
        for i in range(3):
            yield onp.full((2, 2), i, onp.float32)

    got = list(DevicePrefetcher(fresh_producer(), depth=2))
    assert len(got) == 3
    onp.testing.assert_array_equal(onp.asarray(got[2]),
                                   onp.full((2, 2), 2.0))


def test_lock_witness_detects_cycle_and_self_deadlock():
    w = guards.LockOrderWitness()
    la = guards.WitnessLock("A", witness=w)
    lb = guards.WitnessLock("B", witness=w)

    with la:
        with lb:
            pass
    done = []

    def inverted():
        with lb:
            with la:
                done.append(True)

    t = threading.Thread(target=inverted)
    t.start()
    t.join()
    assert done
    with pytest.raises(guards.LockOrderError, match="cyclic"):
        w.check()
    assert [("A", "B"), ("B", "A")] == sorted(w.edges())
    # re-acquiring a held non-reentrant lock raises instead of hanging
    with la:
        with pytest.raises(guards.LockOrderError, match="re-acquiring"):
            la.acquire()


def test_lock_witness_condition_compatible():
    w = guards.LockOrderWitness()
    lk = guards.WitnessLock("C", witness=w)
    cond = threading.Condition(lk)
    hits = []

    def waiter():
        with cond:
            cond.wait(timeout=5)
            hits.append(1)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        cond.notify_all()
    t.join(5)
    assert hits == [1]
    w.check()                                  # single lock: no cycle


def test_lock_order_stress_serve_checkpoint_prefetcher(
        gpt_model, debug_guards, tmp_path):
    """Run the three threaded subsystems concurrently under witness locks
    and assert the recorded acquisition graph is acyclic — the dynamic
    MX005 contract across serve + checkpoint + prefetcher threads."""
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32).start()
    mgr = mx.checkpoint.CheckpointManager(
        str(tmp_path / "ckpt"), period=1, keep_last=2, keep_best=True,
        blocking=False, extra_state=lambda: {"tick": time.time()})
    errors = []

    def serve_client(i):
        try:
            r = eng.generate(onp.array([1 + i, 2, 3], onp.int32), 4)
            assert r.status == "ok", r.status
        except Exception as e:            # noqa: BLE001 - surfaced below
            errors.append(e)

    def checkpointer():
        try:
            for i in range(3):
                mgr.save(i, metric=float(i))
            mgr.wait()
        except Exception as e:            # noqa: BLE001
            errors.append(e)

    def prefetch_consumer():
        try:
            src = (onp.full((2, 2), i, onp.float32) for i in range(6))
            for _ in DevicePrefetcher(src, depth=2):
                pass
        except Exception as e:            # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=serve_client, args=(i,))
               for i in range(4)]
    threads += [threading.Thread(target=checkpointer),
                threading.Thread(target=prefetch_consumer)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    eng.shutdown()
    assert not errors, errors
    guards.check_lock_order()              # acyclic acquisition graph
    nodes = guards.witness().nodes()
    assert "serve.InferenceEngine._lock" in nodes
    assert "serve.InferenceEngine._compile_lock" in nodes
    assert "checkpoint.CheckpointManager._lock" in nodes
