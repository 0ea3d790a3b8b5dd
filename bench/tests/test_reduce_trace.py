"""bench/reduce_trace.py on a recorded trace and on a hand-made one.

No device metric is read here: the recorded file is data, and what is
checked is the arithmetic (busy/idle union, per-op sums, exposed
collectives), against a second, slower way of computing each."""
import os

import conftest  # noqa: F401
from mxbench import reduce_trace as rt

DATA = os.path.join(conftest.BENCH, "testdata")


def brute_union(intervals, step=1e-6):
    """Union length by painting a timeline (exact to ``step`` per edge)."""
    lo = min(s for s, _ in intervals)
    hi = max(s + d for s, d in intervals)
    n = int((hi - lo) / step) + 2
    painted = bytearray(n)
    for s, d in intervals:
        a, b = int(round((s - lo) / step)), int(round((s + d - lo) / step))
        painted[a:b] = b"\x01" * (b - a)
    return sum(painted) * step


def test_recorded_chunk_program():
    trace = rt.load_json(os.path.join(DATA, "trace_chunk.json"))
    dev = trace["devices"][0]
    ops = dev["lines"]["XLA Ops"]
    assert len(ops) > 500 and len(dev["lines"]["XLA Modules"]) == 1
    busy = rt.busy_s(dev)
    # ops nest (a fusion inside its while) and abut: the union is neither
    # their sum nor the module's span, but it can never pass the span
    module = dev["lines"]["XLA Modules"][0]
    assert busy <= module[2] + 1e-9
    assert abs(busy - brute_union([(s, d) for _, s, d in ops])) \
        < 2e-6 * len(ops) ** 0.5 + 1e-4
    sums = rt.per_op_sums(dev)
    plain = [e for e in ops if dev["ops"][e[0]] not in rt.CONTAINERS]
    assert abs(sum(sums.values()) - sum(d for _, _, d in plain)) < 1e-9
    # the program is found by name, and a pattern that matches nothing
    # gives nothing to read
    assert rt.op_seconds(dev, rt.MODULES_LINE, match_any=["jit_chunk"]) \
        == module[2]
    assert rt.op_events(dev, rt.OPS_LINE, match_all=["no-such-op"]) == []
    assert rt.exposed_collective_s(dev, ["all-reduce", "all-gather"]) is None
    # the window is the harness's annotation: 2 ms lead, then the tail
    lo, hi = rt.bounds(trace)
    assert (lo, hi) == (-0.002, 0.038)
    gaps = rt.idle_gaps(dev, (lo, hi), top=2)
    assert abs(sum(d for _, d in rt.idle_gaps(dev, (lo, hi), top=10 ** 6))
               - ((hi - lo) - busy)) < 1e-9
    tail = max(gaps, key=lambda g: g[1])
    assert rt.host_doing(trace, tail[0] + tail[1] / 2) \
        == "bench.serve.wait_for_due"


def test_handmade_collectives_and_gaps():
    trace = rt.load_json(os.path.join(DATA, "trace_handmade.json"))
    dev = trace["devices"][0]
    # the while (0-9) is an operation on the device: busy is its union
    assert rt.busy_s(dev) == 9.0
    assert rt.mean_busy_s(trace) == 9.0
    # all-reduce 1-4: compute covers 1-2 and 3-4, so 2-3 is exposed (1 s);
    # all-gather-start 6-6.5 hides under fusion.3; all-gather-done 8-9 is
    # alone (1 s). The while holds them and is not compute.
    assert rt.exposed_collective_s(
        dev, ["all-reduce", "all-gather"]) == 2.0
    assert rt.exposed_collective_s(dev, ["reduce-scatter"]) is None
    sums = rt.per_op_sums(dev)
    assert "%while.1" not in sums and sums["%all-reduce.1"] == 3.0
    gaps = rt.idle_gaps(dev, rt.bounds(trace))
    assert gaps == [(9.0, 1.0)]
    out = rt.breakdown(trace)
    assert out["idle_gaps"] == [["bench.train.drain", 1.0]]
    assert out["device_ops"][0][1] == 3.0


def test_opcode_parsing():
    assert rt.opcode_of(
        "%f.2 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(bf16[8] %a), "
        "kind=kLoop") == "fusion"
    assert rt.opcode_of(
        '%t.26 = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8] %x), '
        'custom_call_target="tpu_custom_call"') == "custom-call"
    assert rt.opcode_of("%while.5 = (s32[], bf16[2]{0}) while((s32[], "
                        "bf16[2]{0}) %tuple.1), condition=%c") == "while"
    assert rt.opcode_of("jit_step_fn(109788)") == "jit_step_fn"
