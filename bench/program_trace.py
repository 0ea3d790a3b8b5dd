"""The program's own names in a profiler trace: the ``mx.*`` host spans per
thread, and the scope path (``jax.named_scope``) of every device operation.

``reduce_trace.from_profile`` keeps the device's events by name and, of the
host's, only the benchmark's ``bench.*`` annotations. This module opens the
same ``.xplane.pb`` once more (``<root>/.bench_trace/<cell>/``, which still
exists while the readers run; the result is memoised on the ``run`` dict)
and returns a plain record, which is also what the hand-made record in
``bench/testdata/trace_program_spans.json`` holds:

    {"window": [start_s, dur_s],       # bench.window, on this record's clock
     "threads": [[[name, start_s, dur_s, {attr: value}], ...], ...],
     "programs": {"jit_step_b16(<id>)": {"fusion.7": "jit(step_b16)/..."}}}

**Host spans.** ``mxnet_tpu.profiler.scope`` opens a TraceMe beside every
span; its keyword arguments arrive as the event's stats (``tick``, ``rows``,
``sb`` ...; integers stay integers, booleans become 0/1). One ``threads``
entry is one line of the ``/host:CPU`` plane, one line is one thread. Python
does not give the OS thread its name on every version (both the engine's
thread and the main thread read ``python3`` on the v5e machine), so a thread
is known by the spans it carries, never by its name. Only lines that carry an
``mx.*`` span are kept. ``aligned`` puts the spans on ``reduce_trace``'s clock
through the ``bench.window`` annotation, which both records hold.

**Scope paths.** Looked at by hand on a TPU v5 lite, JAX 0.9.0 (PR 26): an
event of the device's ``XLA Ops`` line is named by its instruction's text
*without* the ``metadata={op_name=...}`` part, and its stats are only
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``:
neither ``tf_op`` nor ``op_name`` nor ``hlo_op`` is there, and
``jax.profiler.ProfileData`` shows no event metadata. The scope path is in
the ``/host:metadata`` plane: one event-metadata entry per executed program,
named as on the ``XLA Modules`` line (``jit_step_b4(4595889807412691761)``),
whose one stat, ``Hlo Proto``, holds the optimised module as a serialised
``xla.HloProto``; there every instruction has ``metadata.op_name``, e.g.
``jit(step_b4)/mx.attn/mx.paged_attention/mx.kv_walk/convert_element_type``
(a fusion carries its root's). ``ProfileData`` does not reach that plane's
metadata, and the protobuf classes for it come only with TensorFlow, so
``hlo_scopes`` walks the protobuf wire format itself, descending only where
it must (field numbers below, checked against ``xplane.proto`` and
``hlo.proto`` of TensorFlow 2.21): a program's module is parsed when a reader
first asks for it, not before.

Without a trace (``run["trace"] is None``: an untraced run, the rehearsal)
``load`` returns ``None`` and so does every reader. Where the program has no
``mx.*`` span at all (a commit before PR 26) ``threads`` is empty and the
span readers return ``None``: no count of 0 is made up for a program that
cannot be read.
"""
from __future__ import annotations

import glob
import os

from mxbench import reduce_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = "bench.window"
PREFIX = "mx."


# ------------------------------------------------------------------ loading
def load(run):
    """The record of this run's trace, read once; None without a trace."""
    if run.get("trace") is None:
        return None
    if "_program_trace" not in run:
        trace_dir = os.path.join(ROOT, ".bench_trace", run["cell"]["name"])
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        run["_program_trace"] = (aligned(from_file(paths[-1]), run["trace"])
                                 if paths else None)
    return run["_program_trace"]


def from_file(path: str):
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    record = {"window": None, "threads": [],
              "programs": LazyPrograms(hlo_protos(raw))}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    spans.append([name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9, dict(ev.stats)])
                elif name == WINDOW:
                    record["window"] = [ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9]
            if spans:
                record["threads"].append(spans)
    return record


def aligned(record, trace):
    """The record with its spans on the clock of ``trace`` (a
    ``reduce_trace`` record): both hold the ``bench.window`` annotation, and
    the difference of its two starts is the shift."""
    if record["window"] is None:
        return record
    shift = reduce_trace.bounds(trace)[0] - record["window"][0]
    out = dict(record)
    out["window"] = [record["window"][0] + shift, record["window"][1]]
    out["threads"] = [[[n, s + shift, d, a] for n, s, d, a in spans]
                      for spans in record["threads"]]
    return out


# ------------------------------------------------ protobuf wire format, read
def _varint(buf, i):
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message's top level. A
    length-delimited value is a ``memoryview`` into ``buf`` (nothing is
    copied, nothing below it is parsed)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in a profile")
        yield number, wire, value


def _first(buf, number):
    for num, _, value in fields(buf):
        if num == number:
            return value
    return None


def hlo_protos(raw: bytes):
    """{program as the ``XLA Modules`` line names it: its serialised
    ``HloProto``} from the ``/host:metadata`` plane. XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4 (a map: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6."""
    out = {}
    for num, _, plane in fields(memoryview(raw)):
        if num != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name) != b"/host:metadata":
            continue
        for pnum, _, entry in fields(plane):
            if pnum != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            program, proto = None, None
            for mnum, _, value in fields(meta):
                if mnum == 2:
                    program = bytes(value).decode()
                elif mnum == 5:
                    blob = _first(value, 6)
                    if blob is not None:
                        proto = blob
            if program and proto is not None:
                out[program] = proto
    return out


SCOPE = "mx."
INHERITED = " [inherited]"
HOPS = 6        # how far hlo_scopes looks for a neighbour that has a scope


def own_scope(path: str) -> bool:
    """Whether a path from ``hlo_scopes`` is the instruction's own: it
    holds one of the program's scopes and was not inherited."""
    return SCOPE in path and not path.endswith(INHERITED)


def _int64s(wire, value):
    """A ``repeated int64`` field's values: packed, or one a field."""
    if wire == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_scopes(proto):
    """{instruction name: its ``metadata.op_name``} over every computation
    of one ``HloProto``. HloProto.hlo_module = 1; HloModuleProto
    .computations = 3; HloComputationProto.instructions = 2;
    HloInstructionProto.name = 1, .metadata = 7, .id = 35, .operand_ids =
    36; OpMetadata.op_name = 2.

    The compiler's own instructions carry no scope: the copies that layout
    assignment puts in front of a consumer (``op_name`` empty, or a
    parameter's, ``values[0]``), the converts it splits off a fusion. On
    the v5e they were 65 % of a decode step's time (PR 26), the f32
    converts of the gathered pages first among them. So an instruction
    whose own path holds no ``mx.`` scope takes the path of the nearest
    instruction of its computation that uses its result and has one
    (breadth first through other such instructions, ``HOPS`` deep),
    failing that of the nearest operand; the path then ends in
    ``[inherited]``. What is left without a scope has none on any path to
    or from it. Inheritance is a heuristic: the pools' layout copies, which
    no scope of the program's asked for, count under ``mx.kv_write``
    because the scatter uses them. ``own_scope`` tells the two apart, and
    ``scope_share`` with ``unnamed`` reports how much of a program's time
    has a scope only this way or not at all."""
    out = {}
    module = _first(proto, 1)
    if module is None:
        return out
    for num, _, comp in fields(module):
        if num != 3:
            continue
        names, paths, operands = {}, {}, {}
        for cnum, _, instr in fields(comp):
            if cnum != 2:
                continue
            name, op_name, uid, ops = None, "", None, []
            for inum, wire, value in fields(instr):
                if inum == 1:
                    name = bytes(value).decode()
                elif inum == 7:
                    path = _first(value, 2)
                    op_name = bytes(path).decode() if path is not None else ""
                elif inum == 35:
                    uid = value
                elif inum == 36:
                    ops.extend(_int64s(wire, value))
            if name is not None:
                names[uid], paths[uid], operands[uid] = name, op_name, ops
        users = {}
        for uid, ops in operands.items():
            for op in ops:
                users.setdefault(op, []).append(uid)
        for uid, name in names.items():
            path = paths[uid]
            if SCOPE not in path:
                found = (_nearest_scoped(uid, users, paths)
                         or _nearest_scoped(uid, operands, paths))
                if found:
                    path = found + INHERITED
            out[name] = path
    return out


def _nearest_scoped(start, edges, paths):
    seen, frontier = {start}, [start]
    for _ in range(HOPS):
        nxt = []
        for uid in frontier:
            for other in edges.get(uid, ()):
                if other in seen or other not in paths:
                    continue
                if SCOPE in paths[other]:
                    return paths[other]
                seen.add(other)
                nxt.append(other)
        frontier = nxt
    return None


class LazyPrograms(dict):
    """``programs`` of a record read from a file: a program's module is
    parsed on first use (a 48-layer step has tens of thousands of
    instructions, and a reader wants a few programs of the fifteen)."""

    def __init__(self, protos):
        super().__init__()
        self._protos = protos

    def __contains__(self, program):
        return program in self._protos or super().__contains__(program)

    def __missing__(self, program):
        self[program] = hlo_scopes(self._protos[program]) \
            if program in self._protos else {}
        return self[program]


def scope_of(record, program: str, op: str):
    """The scope path of operation ``op`` (named as ``reduce_trace`` names
    it: ``%fusion.7``, or ``%fusion.7#2`` where two programs have one) of
    ``program``; ``""`` where the instruction has none; None where the
    program's module is not in the record."""
    programs = record["programs"]
    if program not in programs:
        return None
    return programs[program].get(op.lstrip("%").split("#", 1)[0], "")


# --------------------------------------------------------------- arithmetic
def spans_named(record, name: str):
    """``(thread index, [start, dur, attrs])`` of every span called
    ``name``."""
    return [(t, [s, d, a]) for t, spans in enumerate(record["threads"])
            for n, s, d, a in spans if n == name]


def inside(span, lo_hi) -> bool:
    return lo_hi[0] <= span[0] and span[0] + span[1] <= lo_hi[1]


def covered_s(lo: float, hi: float, intervals) -> float:
    """Seconds of ``[lo, hi)`` that the union of ``(start, dur)`` covers."""
    return reduce_trace.union_s(
        (max(s, lo), min(s + d, hi) - max(s, lo))
        for s, d in intervals if s < hi and s + d > lo)


def quantile(values, q: float) -> float:
    """The ``q``-th percentile, interpolated between neighbours (as
    ``readers/percentile.py`` does)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def matches(attrs, where) -> bool:
    """``where`` on a span's attributes: ``{"rows_min": 1}`` asks for
    ``rows >= 1``. A span without the attribute does not match."""
    for key, least in where.items():
        name, _, how = key.rpartition("_")
        if how != "min":
            raise ValueError(f"where {key!r}: only <attribute>_min is known")
        if name not in attrs or float(attrs[name]) < least:
            return False
    return True


def self_s(record, thread: int, span, children=None) -> float:
    """A span's duration less the part that the spans nested in it on the
    same line cover (only those named in ``children``, where given)."""
    lo, hi = span[0], span[0] + span[1]
    nested = [(s, d) for n, s, d, _ in record["threads"][thread]
              if lo <= s and s + d <= hi and (s, d) != (span[0], span[1])
              and (children is None or n in children)]
    return span[1] - covered_s(lo, hi, nested)


def device_of(trace):
    """The device whose idle time the idle metrics speak of: the one
    ``device_idle`` reads, which is the least busy."""
    return min(trace["devices"], key=reduce_trace.busy_s)


def idle_inside_s(trace, intervals) -> float:
    """Seconds of the device's idle gaps inside the window that lie inside
    the union of ``intervals``."""
    dev = device_of(trace)
    gaps = reduce_trace.idle_gaps(dev, reduce_trace.bounds(trace),
                                  top=10 ** 9)
    spans = [(lo, hi - lo) for lo, hi in reduce_trace.merged(intervals)]
    return sum(covered_s(g, g + d, spans) for g, d in gaps)


def program_ops(trace, modules):
    """``(program, its seconds, [(op, seconds), ...])`` for every executed
    program whose name holds one of ``modules``, busiest device: the
    operations (containers left out) that ran inside the program's own
    interval."""
    dev = max(trace["devices"], key=reduce_trace.busy_s)
    progs = sorted(reduce_trace.op_events(
        dev, reduce_trace.MODULES_LINE, match_any=list(modules)),
        key=lambda e: e[1])
    ops = sorted(reduce_trace.op_events(dev, reduce_trace.OPS_LINE),
                 key=lambda e: e[1])
    out, j = [], 0
    for name, lo, dur in progs:
        while j < len(ops) and ops[j][1] < lo:
            j += 1
        mine, k = [], j
        while k < len(ops) and ops[k][1] < lo + dur:
            mine.append((ops[k][0], ops[k][2]))
            k += 1
        out.append((dev["stats"].get(name, name), dur, mine))
    return out


def scope_seconds(record, trace, modules):
    """Over the programs that match: their summed seconds, and
    {scope path: summed seconds of the operations under it} (``""``: under
    no scope). None where no matching program ran or none of them has its
    module in the record."""
    total, by_path, found = 0.0, {}, False
    for program, dur, ops in program_ops(trace, modules):
        total += dur
        for op, seconds in ops:
            path = scope_of(record, program, op)
            if path is None:
                break
            found = True
            by_path[path] = by_path.get(path, 0.0) + seconds
    if not found or total <= 0.0:
        return None
    return total, by_path
