"""The program's spans, scopes and compile counter (PR 26): what the
benchmark's per-layer readers (bench/program_trace.py) find in a profiler
trace is there, under the names PERF.md section 3 lists.

One parametrised test, one case per span or scope. The traced runs and the
lowerings are made once a module, on the CPU: nothing here is a timing."""
import glob
import os
import re
import logging
import sys
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
import run as harness  # noqa: E402  (sets up the ``mxbench`` alias)

TICK_CHILDREN = ["admit", "prefill_dispatch", "prefill_sync", "lease",
                 "decode_dispatch", "decode_sync", "emit", "fetch"]
SYNCS = ["prefill_sync", "decode_sync"]
TRAIN_SPANS = ["step", "h2d", "dispatch", "loss_sync"]
# attributes that each span carries, most known only inside it and set there
SET_INSIDE = ["tick.rows", "tick.sb", "prefill_dispatch.start",
              "prefill_dispatch.end", "prefill_dispatch.final",
              "admit.admitted", "emit.tokens",
              "prefill_dispatch.walk", "prefill_dispatch.of",
              "decode_dispatch.walk", "decode_dispatch.of",
              "prefill_dispatch.filtered", "decode_dispatch.filtered",
              "prefill_dispatch.program", "prefill_dispatch.idle",
              "decode_dispatch.program", "decode_dispatch.idle",
              "prefill_sync.program", "prefill_sync.age_us",
              "decode_sync.program", "decode_sync.age_us"]
SLOW_TICK_FIELDS = ["once", "children", "fetch", "waits", "cpu_s",
                    "heartbeat_late_s"]
# a hand-made record for bench/readers/span_attr_share.py: dispatch spans
# (start, duration, attributes) inside a window of [0, 10) s
IDLE_SPANS = {
    "share": [(1, {"idle": 1}), (2, {"idle": 0}), (3, {"idle": 1}),
              (4, {"idle": 0}), (11, {"idle": 1})],      # the last outside
    "passed_over": [(1, {"idle": 1}), (2, {"idle": 0}), (3, {"idle": 0}),
                    (4, {"idle": 0}), (5, {})],
    "none_left": [(1, {}), (2, {"walk": 3}), (11, {"idle": 1})]}
IDLE_SHARE = {"share": 50.0, "passed_over": 25.0, "none_left": None}
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
         + [("step_name", "jit_step_b2"), ("train_name", "jit_train_step")]
         + [("fetch_in_sync", s) for s in SYNCS]
         + [("heartbeat", "own_thread"), ("host_pause", "held_lock")]
         + [("span_attr_share", c) for c in IDLE_SPANS])


def attr_share(case):
    """``span_attr_share`` over the hand-made spans of ``IDLE_SPANS``."""
    from mxbench.readers import span_attr_share
    names = ["mx.serve.prefill_dispatch", "mx.serve.decode_dispatch"]
    spans = [[names[i % 2], start, 0.5, attrs]
             for i, (start, attrs) in enumerate(IDLE_SPANS[case])]
    run = {"trace": {"host": [("bench.window", 0.0, 10.0)]},
           "_program_trace": {"window": [0.0, 10.0], "threads": [spans],
                              "programs": {}}}
    return span_attr_share.read(run, {"spans": names, "attr": "idle"})


def hold_interpreter(seconds):
    """Keep the interpreter's lock on this thread for ``seconds``: no other
    thread of the process runs meanwhile."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(1.0)
    try:
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            pass
    finally:
        sys.setswitchinterval(old)


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
        assert values
        if attr == "program":
            # the executable, as the device trace's modules line names it
            assert all(re.fullmatch(r"jit_(chunk_c|prefill_b|step_b)\d+", v)
                       for v in values)
        else:
            assert max(values) >= 1
        if attr == "idle":
            assert set(values) <= {0, 1}
    elif kind == "fetch_in_sync":
        # the copy home, after the wait for the chip, inside each wait
        line = engine_line(request.getfixturevalue("serve_lines"))
        fetches = [(lo, hi) for n, lo, hi, _ in line
                   if n == "mx.serve.fetch"]
        syncs = [(lo, hi) for n, lo, hi, _ in line
                 if n == f"mx.serve.{name}"]
        assert syncs
        for lo, hi in syncs:
            assert sum(lo <= f_lo and f_hi <= hi
                       for f_lo, f_hi in fetches) == 1
    elif kind == "heartbeat":
        # one thread of its own, which carries nothing else
        lines = request.getfixturevalue("serve_lines")
        beating = [ln for ln in lines
                   if any(n == "mx.serve.heartbeat" for n, *_ in ln)]
        assert len(beating) == 1
        assert all(n == "mx.serve.heartbeat" for n, *_ in beating[0])
    elif kind == "host_pause":
        eng = request.getfixturevalue("engine")
        before = eng.stats()["host_pauses"]
        recorder.RECORDER.reset()
        hold_interpreter(0.4)
        time.sleep(0.2)                 # the heartbeat writes its event
        pauses = [e for e in recorder.RECORDER.snapshot()
                  if e["name"] == "serve.host_pause"]
        assert pauses
        ev = max(pauses, key=lambda e: e["late_s"])
        # a thread ran and held the interpreter: the process spent CPU
        assert ev["late_s"] > engine_mod._PAUSE_S and ev["cpu_s"] > 0
        assert ev["gc"] >= 0 and "tick" in ev and "span" in ev
        stats = eng.stats()
        assert stats["host_pauses"] >= before + 1
        assert stats["host_pause_max_s"] >= ev["late_s"]
    elif kind == "span_attr_share":
        assert attr_share(name) == IDLE_SHARE[name]
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


class _Messages(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


@pytest.fixture(scope="module")
def slow_tick(engine):
    """One tick held 0.6 s by a sleep before its decode step, with every
    wait for the device listed (``_SLOW_WAIT_S`` 0): the ``serve.slow_tick``
    events written and the warnings logged while its request ran."""
    real, slept = engine._step_tick, []

    def slow_once():
        if not slept:
            slept.append(time.sleep(0.6))
        real()
    slept.append(None)                    # armed after the warm request
    log = _Messages()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_SLOW_TICK_S", 0.5)
        mp.setattr(engine_mod, "_SLOW_WAIT_S", 0.0)
        mp.setattr(engine, "_step_tick", slow_once)
        # "compilations since the previous tick" counts the idle time
        # before this one too: a request first, so that the tick before is
        # recent
        assert engine.submit(onp.arange(6) % 64, 2).result(120).ok
        slept.clear()
        recorder.RECORDER.reset()
        engine_mod.logger.addHandler(log)
        try:
            assert engine.submit(onp.arange(6) % 64, 4).result(120).ok
        finally:
            engine_mod.logger.removeHandler(log)
    events = [e for e in recorder.RECORDER.snapshot()
              if e["name"] == "serve.slow_tick"]
    return events, log.messages


@pytest.mark.parametrize("field", SLOW_TICK_FIELDS)
def test_slow_tick_is_recorded(field, slow_tick):
    events, messages = slow_tick
    assert len(events) == 1
    ev = events[0]
    if field == "once":
        assert ev["seconds"] >= 0.6 and ev["tick"] >= 1
        assert ev["compiles"] == 0
        assert sum("slow tick" in m for m in messages) == 1
    elif field == "children":
        assert "mx.serve.admit" in ev["children"]
        # fetch lies inside a sync: counted there already
        assert sum(v for k, v in ev["children"].items()
                   if k != "mx.serve.fetch") < ev["seconds"]
    elif field == "fetch":
        children = ev["children"]
        assert 0 < children["mx.serve.fetch"] <= sum(
            children.get(f"mx.serve.{s}", 0.0) for s in SYNCS)
    elif field == "waits":
        # [span, program, age_ms, seconds, fetch_s] of each wait
        assert ev["waits"]
        for span, program, age_ms, seconds, fetch_s in ev["waits"]:
            assert span in SYNCS and program.startswith("jit_")
            assert age_ms >= 0 and seconds >= fetch_s >= 0
    elif field == "cpu_s":
        assert ev["cpu_s"] >= 0
    elif field == "heartbeat_late_s":
        assert ev["heartbeat_late_s"] >= 0
