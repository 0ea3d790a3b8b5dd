"""From a profiler trace to numbers: device busy time, per-operation sums,
exposed collective time, the longest idle gaps and what the host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. ``load_dir``
turns it into a plain record, which is also what the recorded trace in
``bench/testdata/`` holds, so the arithmetic below is checked on the CPU:

    {"window_s": float,
     "devices": [{"name": str, "lines": {line: [[name, start_s, dur_s], ..]},
                  "stats": {name: "the instruction's whole text"},
                  "ops": {name: "its opcode"}}],
     "host": [[annotation, start_s, dur_s], ...]}

Times are seconds from the earliest event of the trace. A TPU's device plane
is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed HLO
operation, named by the instruction's whole text (a ``while`` or ``call``
holds its body's events nested inside it)
and its ``XLA Modules`` line one per executed program.
"""
from __future__ import annotations

import glob
import json
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that only hold other operations: their own time is their
#: children's, so they are neither compute nor a kernel
CONTAINERS = ("while", "conditional", "call")


# ------------------------------------------------------------------ loading
def load_dir(trace_dir: str, chips: int, window_s: float | None = None):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    from jax.profiler import ProfileData
    data = ProfileData.from_file(paths[-1])
    return from_profile(data, chips, window_s)


def from_profile(data, chips: int, window_s: float | None = None):
    devices, host, t_min = [], [], None
    planes = list(data.planes)
    dev_planes = [p for p in planes if p.name.startswith("/device:TPU:")
                  and p.name[len("/device:TPU:"):].isdigit()]
    dev_planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in dev_planes[:chips]:
        rec = {"name": plane.name, "lines": {}, "stats": {}, "ops": {}}
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            short = {}
            for ev in line.events:
                text = ev.name
                name = short.get(text)
                if name is None:
                    # two programs may both have a %fusion.3: keep them apart
                    name, k = text.split(" = ", 1)[0], 1
                    while name in rec["stats"]:
                        k += 1
                        name = f"{text.split(' = ', 1)[0]}#{k}"
                    short[text] = name
                    rec["stats"][name] = text
                    rec["ops"][name] = opcode_of(text)
                events.append([name, ev.start_ns, ev.duration_ns])
            rec["lines"][line.name] = events
            if events:
                lo = min(e[1] for e in events)
                t_min = lo if t_min is None else min(t_min, lo)
        devices.append(rec)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host.append([ev.name, ev.start_ns, ev.duration_ns])
    t_min = t_min or 0
    for rec in devices:
        for events in rec["lines"].values():
            for e in events:
                e[1] = (e[1] - t_min) * 1e-9
                e[2] = e[2] * 1e-9
    for e in host:
        e[1] = (e[1] - t_min) * 1e-9
        e[2] = e[2] * 1e-9
    trace = {"window_s": window_s, "devices": devices, "host": host}
    if window_s is None:
        trace["window_s"] = span_s(trace)
    return trace


def opcode_of(text: str) -> str:
    """The opcode of an HLO instruction as the trace prints it:
    ``%name = <type> opcode(operands), attributes``. A program on the
    modules line has no `` = `` and is its own opcode."""
    if " = " not in text:
        return text.split("(", 1)[0]
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0].strip()


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------- arithmetic
def union_s(intervals) -> float:
    """Length of the union of ``(start, duration)`` intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, dur in sorted(intervals):
        hi = lo + dur
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def merged(intervals):
    """The union as a sorted list of disjoint ``(lo, hi)``."""
    out = []
    for lo, dur in sorted(intervals):
        hi = lo + dur
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def _is_container(dev, name: str) -> bool:
    return dev.get("ops", {}).get(name, "") in CONTAINERS


def busy_s(dev) -> float:
    """Seconds in which some operation ran on the device."""
    ops = dev["lines"].get(OPS_LINE, [])
    return union_s((s, d) for _, s, d in ops)


def mean_busy_s(trace) -> float:
    devs = trace["devices"]
    return sum(busy_s(d) for d in devs) / len(devs) if devs else 0.0


def span_s(trace) -> float:
    lo, hi = None, None
    for dev in trace["devices"]:
        for events in dev["lines"].values():
            for _, s, d in events:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    return (hi - lo) if lo is not None else 0.0


def window_s(trace) -> float:
    return float(trace["window_s"])


def _matches(name, stats, match_all, match_any):
    text = f"{name} {stats}"
    if match_all and not all(m in text for m in match_all):
        return False
    if match_any and not any(m in text for m in match_any):
        return False
    return True


def op_events(dev, line, match_all=(), match_any=()):
    """Events of ``line`` whose name or string stats match."""
    verdict = {}
    out = []
    for name, s, d in dev["lines"].get(line, []):
        ok = verdict.get(name)
        if ok is None:
            ok = verdict[name] = (not _is_container(dev, name)) and _matches(
                name, dev["stats"].get(name, ""), match_all, match_any)
        if ok:
            out.append((name, s, d))
    return out


def op_seconds(dev, line, match_all=(), match_any=()) -> float:
    return sum(d for _, _, d in op_events(dev, line, match_all, match_any))


def per_op_sums(dev, line=OPS_LINE):
    """{operation name: summed seconds}, containers left out."""
    sums = {}
    for name, _, d in dev["lines"].get(line, []):
        if _is_container(dev, name):
            continue
        sums[name] = sums.get(name, 0.0) + d
    return sums


def exposed_collective_s(dev, collectives):
    """Seconds inside collective operations during which no compute
    operation ran on the device; None where the device ran no collective."""
    coll, compute = [], []
    for name, s, d in dev["lines"].get(OPS_LINE, []):
        if _is_container(dev, name):
            continue
        if any(c in dev.get("ops", {}).get(name, name) for c in collectives):
            coll.append((s, d))
        else:
            compute.append((s, d))
    if not coll:
        return None
    c_union = merged(coll)
    k_union = merged(compute)
    exposed, j = 0.0, 0
    for lo, hi in c_union:
        covered = 0.0
        while j < len(k_union) and k_union[j][1] <= lo:
            j += 1
        i = j
        while i < len(k_union) and k_union[i][0] < hi:
            covered += min(hi, k_union[i][1]) - max(lo, k_union[i][0])
            i += 1
        exposed += (hi - lo) - covered
    return exposed


def bounds(trace):
    """(start, end) of the traced window on the trace's clock: the harness's
    own ``bench.window`` annotation where the trace holds it, else from the
    first device event for ``window_s``."""
    for name, s, d in trace["host"]:
        if name == "bench.window":
            return s, s + d
    return 0.0, window_s(trace)


def idle_gaps(dev, lo_hi, top: int = 10):
    """The longest gaps between busy intervals inside ``lo_hi``:
    ``(start, seconds)``. The window's own edges count."""
    t_lo, t_hi = lo_hi
    busy = merged((s, d) for _, s, d in dev["lines"].get(OPS_LINE, []))
    gaps, prev = [], t_lo
    for lo, hi in busy:
        if lo > prev:
            gaps.append((prev, lo - prev))
        prev = max(prev, hi)
    if t_hi > prev:
        gaps.append((prev, t_hi - prev))
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def host_doing(trace, t: float) -> str:
    """The innermost of the benchmark's own annotations that covers ``t``."""
    best, best_dur = "unannotated", None
    for name, s, d in trace["host"]:
        if name == "bench.window":
            continue
        if s <= t <= s + d and (best_dur is None or d < best_dur):
            best, best_dur = name, d
    return best


def breakdown(trace, top: int = 10):
    """The contract's optional ``breakdown``: the operations that took most
    device time (summed, busiest device) and the longest idle gaps by what
    the host was doing at their middle."""
    if not trace["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    dev = max(trace["devices"], key=busy_s)
    sums = sorted(per_op_sums(dev).items(), key=lambda kv: -kv[1])[:top]
    gaps = [[host_doing(trace, s + d / 2), d]
            for s, d in idle_gaps(dev, bounds(trace), top)]
    return {"device_ops": [[label(dev, n), t] for n, t in sums],
            "idle_gaps": gaps}


def label(dev, name: str) -> str:
    """A short name for an operation: its own name, its opcode (a Pallas
    kernel says so) and the type it produces."""
    text = dev["stats"].get(name, "")
    op = dev.get("ops", {}).get(name, "")
    if 'custom_call_target="tpu_custom_call"' in text:
        op = "tpu_custom_call"
    out = text.split(" = ", 1)[1][:48] if " = " in text else ""
    return f"{name} {op} {out}".strip()[:120]


def sample(trace, t_hi: float):
    """The trace's record cut to the events that end before ``t_hi``: what
    ``bench/testdata/`` keeps of a real trace."""
    out = {"window_s": t_hi, "devices": [], "host": [
        e for e in trace["host"] if e[1] + e[2] <= t_hi]}
    for dev in trace["devices"]:
        lines = {k: [e for e in v if e[1] + e[2] <= t_hi]
                 for k, v in dev["lines"].items()}
        names = {e[0] for v in lines.values() for e in v}
        out["devices"].append({
            "name": dev["name"], "lines": lines,
            "stats": {n: dev["stats"][n][:400] for n in names},
            "ops": {n: dev["ops"][n] for n in names}})
    return out


def summary(trace, top: int = 40):
    """For a person looking at one trace by hand."""
    out = {"window_s": trace["window_s"], "host_annotations": len(trace["host"]),
           "devices": []}
    for dev in trace["devices"]:
        sums = sorted(per_op_sums(dev).items(), key=lambda kv: -kv[1])[:top]
        mods = {}
        for name, _, d in dev["lines"].get(MODULES_LINE, []):
            m = mods.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += d
        out["devices"].append({
            "name": dev["name"], "busy_s": busy_s(dev),
            "events": {k: len(v) for k, v in dev["lines"].items()},
            "modules": mods,
            "top_ops": [[n, t, dev["stats"].get(n, "")[:300]]
                        for n, t in sums]})
    return out
