"""bench/program_trace.py and its three readers on a hand-made record.

No device metric is read here: ``testdata/trace_program_spans.json`` is data
(its ``note`` says what it holds), and every expected number below is worked
out from its intervals by hand."""
import copy
import importlib
import json
import os

import pytest

import conftest  # noqa: F401
from mxbench import program_trace as pt

DATA = os.path.join(conftest.BENCH, "testdata", "trace_program_spans.json")


def reader(name):
    return importlib.import_module(f"mxbench.readers.{name}").read


@pytest.fixture
def run():
    with open(DATA) as f:
        doc = json.load(f)
    return {"trace": doc["trace"], "cell": {"name": "handmade"},
            "_program_trace": pt.aligned(doc["program"], doc["trace"])}


def test_clock_alignment_through_bench_window(run):
    with open(DATA) as f:
        doc = json.load(f)
    raw = doc["program"]
    assert raw["window"][0] == 100.0            # its own clock
    rec = run["_program_trace"]
    assert rec["window"] == [0.0, 1.0]          # the trace's
    tick = pt.spans_named(rec, "mx.serve.tick")[0][1]
    assert abs(tick[0] - 0.08) < 1e-9 and tick[1] == 0.32
    # a record without the annotation is left where it is
    bare = dict(raw, window=None)
    assert pt.aligned(bare, doc["trace"]) is bare


TICK = {"span": "mx.serve.tick", "where": {"rows_min": 1}}
SYNCS = ["mx.serve.prefill_sync", "mx.serve.decode_sync"]


@pytest.mark.parametrize("args, expected", [
    # ticks 1 and 2 decoded rows, tick 3 (a prefill only) carries none
    (dict(TICK, stat="count"), 2),
    (dict(span="mx.serve.tick", stat="count"), 3),
    (dict(TICK, stat="p100"), 320.0),
    (dict(TICK, stat="p50"), 230.0),            # between 140 and 320
    # self time: tick 1 is 0.32 less syncs of 0.08 + 0.06, tick 2 is 0.14
    # less 0.07; with every nested span tick 1 loses 0.03 more
    (dict(TICK, stat="self_p100", children=SYNCS), 180.0),
    (dict(TICK, stat="self_p50", children=SYNCS), 125.0),
    (dict(TICK, stat="self_p100"), 150.0),
    (dict(span="mx.serve.prefill_sync", stat="p50"), 60.0),
    # the fifth idle wait runs past the window's end, so four are inside it,
    # and of the two compilations one lies before the window
    (dict(span="mx.serve.idle", stat="count"), 5),
    (dict(span="mx.compile", stat="count"), 1),
    # spans there are, but none of this name: 0 is a count, no duration
    (dict(span="mx.serve.lease", stat="count"), 0),
    (dict(span="mx.serve.lease", stat="p50"), None),
    (dict(TICK, stat="count", where={"rows_min": 3}), 0),
])
def test_span_stat(run, args, expected):
    got = reader("span_stat")(run, args)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, abs=1e-6)


def test_idle_split_between_tick_and_idle(run):
    idle = reader("idle_under_span")
    # the device idles 0.10 + 0.11 + 0.15 + 0.41 of the 1 s window
    assert reader("device_idle")(run, {}) == pytest.approx(77.0)
    under_tick = idle(run, {"span": "mx.serve.tick"})
    under_idle = idle(run, {"span": "mx.serve.idle"})
    assert under_tick == pytest.approx(24.0)
    assert under_idle == pytest.approx(53.0)
    # every idle second lies under the engine's one or the other span
    assert under_tick + under_idle == pytest.approx(77.0)
    assert idle(run, {"span": "mx.serve.lease"}) == 0.0


@pytest.mark.parametrize("args, expected", [
    # two steps of 0.10 s; in each 0.04 under the walk, 0.03 the sampler,
    # and of the pools' two copies, 0.015 under no scope and 0.005 under
    # the scatter's by inheritance
    (dict(scope="mx.paged_attention", modules=["jit_step"]), 45.0),
    (dict(scope="mx.kv_write", modules=["jit_step"]), 5.0),
    (dict(unnamed=True, modules=["jit_step"]), 20.0),
    (dict(unnamed=True, modules=["jit_prefill"]), 0.0),
    (dict(scope="mx.kv_walk", modules=["jit_step"]), 40.0),
    (dict(scope="mx.sample", modules=["jit_step"]), 30.0),
    (dict(scope=["mx.sample", "mx.attn"], modules=["jit_step"]), 75.0),
    # %fusion.1#2 is the prefill's fusion.1, not the step's
    (dict(scope="mx.lm_head", modules=["jit_prefill"]), 100.0),
    (dict(scope="mx.lm_head", modules=["jit_step"]), None),
    (dict(scope="mx.sample", modules=["jit_train_step"]), None),
])
def test_scope_share(run, args, expected):
    got = reader("scope_share")(run, args)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_unscoped_time_is_a_path_of_its_own(run):
    total, by_path = pt.scope_seconds(run["_program_trace"], run["trace"],
                                      ["jit_step"])
    assert total == pytest.approx(0.2)
    assert by_path[""] == pytest.approx(0.03)        # two pool copies
    assert not pt.own_scope("") and not pt.own_scope("jit(step_b2)/add")
    inherited = [p for p in by_path if p.endswith(pt.INHERITED)]
    assert len(inherited) == 1 and not pt.own_scope(inherited[0])
    assert by_path[inherited[0]] == pytest.approx(0.01)
    assert sum(by_path.values()) == pytest.approx(0.18)


@pytest.mark.parametrize("name, args", [
    ("span_stat", {"span": "mx.serve.tick", "stat": "count"}),
    ("idle_under_span", {"span": "mx.serve.tick"}),
    ("scope_share", {"scope": "mx.sample", "modules": ["jit_step"]}),
    ("scope_share", {"unnamed": True, "modules": ["jit_step"]}),
])
def test_nothing_to_read(run, name, args):
    # no trace: an untraced run, the rehearsal
    assert reader(name)({"trace": None, "cell": {"name": "x"}}, args) is None
    # a program from before PR 26: no mx.* span, no mx.* scope
    old = copy.deepcopy(run)
    old["_program_trace"]["threads"] = []
    old["_program_trace"]["programs"] = {
        k: {op: "jit(step)/add" for op in v}
        for k, v in old["_program_trace"]["programs"].items()}
    assert reader(name)(old, args) is None


# ------------------------------------------------------ the wire format
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def ld(number, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def test_scope_paths_from_a_serialised_profile():
    def instr(name, uid, op_name, operands=()):
        meta = b"" if op_name is None else ld(
            7, ld(1, "fusion") + ld(2, op_name) + ld(3, "gpt.py"))
        packed = b"".join(varint(o) for o in operands)
        return (ld(1, name) + ld(2, "fusion") + meta
                + varint(35 << 3) + varint(uid)
                + (ld(36, packed) if operands else b""))
    # param -> copy.3 (the compiler's, no scope) -> fusion.7 (mx.mlp)
    # -> bitcast.1 (none) -> tuple.2 (none); lone.9 touches nothing scoped
    comp = (ld(1, "main")
            + ld(2, instr("p.0", 1, "values[3]"))
            + ld(2, instr("copy.3", 2, None, [1]))
            + ld(2, instr("fusion.7", 3, "jit(step_b4)/mx.mlp/dot", [2]))
            + ld(2, instr("bitcast.1", 4, "", [3]))
            + ld(2, instr("tuple.2", 5, None, [4]))
            + ld(2, instr("lone.9", 6, "jit(step_b4)/add"))
            + varint(5 << 3) + varint(300))
    proto = ld(1, ld(1, "jit_step_b4") + ld(3, comp))
    stat = varint(1 << 3) + varint(9) + ld(6, proto)
    meta = varint(1 << 3) + varint(4) + ld(2, "jit_step_b4(42)") + ld(5, stat)
    plane = (varint(1 << 3) + varint(2) + ld(2, "/host:metadata")
             + ld(4, varint(1 << 3) + varint(4) + ld(2, meta)))
    other = ld(2, "/host:CPU") + ld(3, ld(2, "python3"))
    raw = ld(1, other) + ld(1, plane)
    protos = pt.hlo_protos(raw)
    assert list(protos) == ["jit_step_b4(42)"]
    programs = pt.LazyPrograms(protos)
    assert "jit_step_b4(42)" in programs and not programs.keys()
    record = {"programs": programs}
    own = "jit(step_b4)/mx.mlp/dot"
    assert pt.scope_of(record, "jit_step_b4(42)", "%fusion.7#3") == own
    # the copy in front of the fusion and the parameter behind it take the
    # scope of what uses them; what follows the fusion, of what feeds it
    for op in ("%copy.3", "%p.0", "%bitcast.1", "%tuple.2"):
        assert pt.scope_of(record, "jit_step_b4(42)", op) \
            == own + pt.INHERITED
    assert pt.scope_of(record, "jit_step_b4(42)", "%lone.9") \
        == "jit(step_b4)/add"
    assert pt.scope_of(record, "jit_step_b4(42)", "%no.such") == ""
    assert pt.scope_of(record, "jit_chunk(1)", "%fusion.7") is None
