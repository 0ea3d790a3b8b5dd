"""The program's spans, scopes and compile counter (PR 26): what the
benchmark's per-layer readers (bench/program_trace.py) find in a profiler
trace is there, under the names PERF.md section 3 lists.

One parametrised test, one case per span or scope. The traced runs and the
lowerings are made once a module, on the CPU: nothing here is a timing."""
import glob
import re
import logging
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.models import GPTModel
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.observability import recorder
from mxnet_tpu.serve import InferenceEngine
from mxnet_tpu.serve import engine as engine_mod

TICK_CHILDREN = ["admit", "prefill_dispatch", "prefill_sync", "lease",
                 "decode_dispatch", "decode_sync", "emit"]
TRAIN_SPANS = ["step", "h2d", "dispatch", "loss_sync"]
# attributes that are known only inside the span and set on it there
SET_INSIDE = ["tick.rows", "tick.sb", "prefill_dispatch.start",
              "prefill_dispatch.end", "prefill_dispatch.final",
              "admit.admitted", "emit.tokens",
              "prefill_dispatch.walk", "prefill_dispatch.of",
              "decode_dispatch.walk", "decode_dispatch.of",
              "prefill_dispatch.filtered", "decode_dispatch.filtered"]
STEP_SCOPES = ["mx.embed", "mx.attn", "mx.paged_attention", "mx.kv_write",
               "mx.kv_walk", "mx.mlp", "mx.lm_head", "mx.sample"]
TRAIN_SCOPES = ["mx.embed", "mx.attn", "mx.mlp", "mx.lm_head", "mx.loss",
                "mx.optimizer"]


def tiny_gpt():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    return net


def host_lines(trace_dir):
    """[[(name, start_ns, end_ns, attrs)] per host line] of the ``mx.*``
    annotations in a profile."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
                     for e in line.events if e.name.startswith("mx.")]
            if spans:
                lines.append(spans)
    return lines


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(tiny_gpt(), max_batch_size=2, max_len=32,
                          page_size=8)
    eng.warmup()
    eng.start()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def serve_lines(engine, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(d)
    try:
        for wave in range(2):
            hs = [engine.submit(onp.arange(5 + i) % 64, 5,
                                temperature=0.7 * i, top_p=0.9, seed=i)
                  for i in range(3)]
            assert all(h.result(120).ok for h in hs)
            time.sleep(0.25)            # the engine idles between the waves
    finally:
        jax.profiler.stop_trace()
    return host_lines(d)


@pytest.fixture(scope="module")
def train_step():
    from mxnet_tpu.parallel import P
    dp = 2
    mesh = parallel.make_mesh({"dp": dp}, devices=jax.devices()[:dp])
    net = tiny_gpt()
    ids = onp.arange(2 * dp * 8).reshape(2 * dp, 8) % 64
    step = parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(), mx.optimizer.Adam(learning_rate=1e-3),
        example_inputs=[mx.np.array(ids, dtype="int32")], mesh=mesh,
        data_spec=P("dp"), label_spec=P("dp"), block_every=1)
    return step, ids


@pytest.fixture(scope="module")
def train_lines(train_step, tmp_path_factory):
    step, ids = train_step
    x, y = mx.np.array(ids, dtype="int32"), mx.np.array(ids, dtype="int32")
    step.step(x, y)
    step.drain()
    d = str(tmp_path_factory.mktemp("train_trace"))
    jax.profiler.start_trace(d)
    try:
        for _ in range(3):
            step.step(x, y)
        step.drain()
    finally:
        jax.profiler.stop_trace()
    return host_lines(d)


@pytest.fixture(scope="module")
def step_text(engine):
    sb = 2
    return engine._get_step(sb).lower(
        *engine._example_args("decode", sb)).as_text(debug_info=True)


@pytest.fixture(scope="module")
def train_text(train_step, train_lines):
    step, _ = train_step
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          step._last_avals)
    return jax.jit(step._step_fn).lower(*shapes).as_text(debug_info=True)


def engine_line(lines):
    (line,) = [ln for ln in lines if any(n == "mx.serve.tick"
                                         for n, *_ in ln)]
    return line


CASES = ([("serve_span", "idle"), ("serve_span", "tick")]
         + [("serve_span", c) for c in TICK_CHILDREN]
         + [("nested_in_tick", c) for c in TICK_CHILDREN]
         + [("idle_outside_tick", "idle")]
         + [("set_inside", a) for a in SET_INSIDE]
         + [("walk_form", "prefill_dispatch"), ("walk_form", "decode_dispatch")]
         + [("train_span", s) for s in TRAIN_SPANS]
         + [("step_scope", s) for s in STEP_SCOPES]
         + [("train_scope", s) for s in TRAIN_SCOPES]
         + [("step_name", "jit_step_b2"), ("train_name", "jit_train_step")])


@pytest.mark.parametrize("kind, name", CASES,
                         ids=[f"{k}-{n}" for k, n in CASES])
def test_program_names(kind, name, request):
    if kind == "serve_span":
        line = engine_line(request.getfixturevalue("serve_lines"))
        assert any(n == f"mx.serve.{name}" for n, *_ in line)
    elif kind == "nested_in_tick":
        line = engine_line(request.getfixturevalue("serve_lines"))
        ticks = [(a["tick"], lo, hi) for n, lo, hi, a in line
                 if n == "mx.serve.tick"]
        mine = [(a["tick"], lo, hi) for n, lo, hi, a in line
                if n == f"mx.serve.{name}"]
        assert mine
        for tick, lo, hi in mine:
            # inside the tick whose number it carries, on the same line
            (t_lo, t_hi), = [(a, b) for t, a, b in ticks if t == tick]
            assert t_lo <= lo and hi <= t_hi
    elif kind == "idle_outside_tick":
        line = engine_line(request.getfixturevalue("serve_lines"))
        ticks = [(lo, hi) for n, lo, hi, _ in line if n == "mx.serve.tick"]
        idles = [(lo, hi) for n, lo, hi, _ in line if n == "mx.serve.idle"]
        assert idles
        assert not any(lo < t_hi and t_lo < hi
                       for lo, hi in idles for t_lo, t_hi in ticks)
    elif kind == "set_inside":
        line = engine_line(request.getfixturevalue("serve_lines"))
        span, attr = name.split(".")
        values = [a[attr] for n, _, _, a in line
                  if n == f"mx.serve.{span}" and attr in a]
        assert values and max(values) >= 1
    elif kind == "walk_form":
        # the form the dispatched program's paged read was traced in; two
        # heads over a bucket of 8 tokens are as few columns as a step's
        line = engine_line(request.getfixturevalue("serve_lines"))
        forms = [a.get("form") for n, _, _, a in line
                 if n == f"mx.serve.{name}"]
        assert forms and set(forms) == {"lanes"}
    elif kind == "train_span":
        lines = request.getfixturevalue("train_lines")
        assert any(n == f"mx.train.{name}" for ln in lines for n, *_ in ln)
    elif kind == "step_scope":
        # a scope inside a function that is jitted by itself heads its own
        # paths in the lowered text; the compiled program joins them
        assert re.search(rf'[/"]{re.escape(name)}/',
                         request.getfixturevalue("step_text"))
    elif kind == "train_scope":
        text = request.getfixturevalue("train_text")
        if name == "mx.optimizer":
            assert f"/{name}/" in text
        else:
            # differentiated: the forward pass reads jvp(<name>), the
            # backward keeps the name inside transpose(jvp(...))
            assert f"/jvp({name})/" in text
            assert f"/transpose(jvp({name}))/" in text
    elif kind == "step_name":
        assert f"@{name}" in request.getfixturevalue("step_text")
    elif kind == "train_name":
        assert f"@{name}" in request.getfixturevalue("train_text")


@pytest.mark.parametrize("call, grows", [("first", 1), ("second", 0)])
def test_compile_counter(call, grows, tmp_path):
    x = jnp.arange(7.0)

    def fresh(v):
        return v * 3.0 + 11.0
    f = jax.jit(fresh)
    if call == "second":
        f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        before = metrics.backend_compiles()
        f(x).block_until_ready()
        assert metrics.backend_compiles() - before == grows
    finally:
        jax.profiler.stop_trace()
    spans = [s for ln in host_lines(str(tmp_path)) for s in ln
             if s[0] == "mx.compile"] if grows else []
    assert len(spans) == grows
    if grows:
        assert spans[0][3]["fun"] == "jit(fresh)"
        events = [e for e in recorder.RECORDER.snapshot()
                  if e["kind"] == "compile" and e["name"] == "jit(fresh)"]
        assert events and events[-1]["seconds"] > 0


def test_slow_tick_is_recorded(engine, monkeypatch, caplog):
    monkeypatch.setattr(engine_mod, "_SLOW_TICK_S", 0.5)
    real, slept = engine._step_tick, []

    def slow_once():
        if not slept:
            slept.append(time.sleep(0.6))
        real()
    slept.append(None)                    # armed after the warm request
    monkeypatch.setattr(engine, "_step_tick", slow_once)
    # "compilations since the previous tick" counts the idle time before
    # this one too: a request first, so that the tick before is recent
    assert engine.submit(onp.arange(6) % 64, 2).result(120).ok
    slept.clear()
    recorder.RECORDER.reset()
    with caplog.at_level(logging.WARNING):
        assert engine.submit(onp.arange(6) % 64, 4).result(120).ok
    events = [e for e in recorder.RECORDER.snapshot()
              if e["name"] == "serve.slow_tick"]
    assert len(events) == 1
    ev = events[0]
    assert ev["seconds"] >= 0.6 and ev["tick"] >= 1 and ev["compiles"] == 0
    assert "mx.serve.admit" in ev["children"]
    assert sum(ev["children"].values()) < ev["seconds"]
    assert sum("slow tick" in r.getMessage() for r in caplog.records) == 1
