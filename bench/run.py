"""The benchmark's one entry point: one process, one cell, once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, builds the system under test with
weights and traffic made from ``--seed``, warms up (all of which is
``setup_s``), measures for ``--seconds``, frees the program's state, checks
what the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output.

Nothing here names a cell, a configuration or a metric: a cell is
``bench/workloads/<cell>.json``, its configuration ``bench/configs/<config>
.json``, its traffic a module under ``bench/traffic/`` named by the cell's
``traffic.kind``, its model a module under ``bench/models/`` named by the
configuration's ``builder`` (which brings the family's reference and its
count of operations and bytes), and each metric ``bench/metrics/<metric>.json``
with the reader under ``bench/readers/`` that it names.

It refuses — exits non-zero and prints no result — where JAX finds no TPU,
where the chip is not in ``bench/peaks.json``, or where the cell asks for
more chips than are present. ``--rehearsal`` is for the cells under
``bench/tests/`` only: it runs the same code on whatever platform is present,
names that platform truthfully, traces nothing and emits no device metric.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time
import types

T_PROCESS_START = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def alias_package(base=BENCH):
    """Make this directory importable as ``mxbench``. The name ``bench`` is
    taken at the root of the repository by ``bench.py``. Modules are looked
    for under ``base`` first (the rehearsal's directory, as ``read_metric``
    does for a metric's file), then here: a builder, its reference and its
    count may live beside the rehearsal cell that uses them."""
    if "mxbench" not in sys.modules:
        pkg = types.ModuleType("mxbench")
        pkg.__path__ = [BENCH]
        sys.modules["mxbench"] = pkg
    path = sys.modules["mxbench"].__path__
    if base not in path:
        path.insert(0, base)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


alias_package()


class Refuse(SystemExit):
    """Exit without a result line."""

    def __init__(self, why: str):
        print(f"bench: refused: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


def note(**fields):
    """An early line of standard output: facts about the run that are not
    the result (the device, the launch tally, what the step compiled to)."""
    print(json.dumps(fields), flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find_cell(benchmark: dict, name: str, base: str):
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            break
    else:
        raise Refuse(f"no workload {name!r} in the benchmark file")
    path = os.path.join(base, "workloads", f"{name}.json")
    spec = load_json(path)
    for key in ("config", "chips"):
        if spec[key] != cell[key]:
            raise Refuse(f"{path}: {key} {spec[key]!r} differs from the "
                         f"benchmark file's {cell[key]!r}")
    config_entry = next(c for c in benchmark["configs"]
                        if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, config_entry["file"]))
    return cell, spec, cfg


def cell_metrics(benchmark: dict, cell_name: str, group: str):
    """The metrics of ``group`` that this cell reports."""
    out = []
    e2e_of_cell = {m["name"] for m in benchmark["end_to_end"]
                   if cell_name in m.get("workloads", [cell_name])}
    for m in benchmark[group]:
        cells = m.get("workloads")
        if cells is None and group == "per_layer":
            if m["moves"] not in e2e_of_cell:
                continue
        elif cells is not None and cell_name not in cells:
            continue
        out.append(m)
    return out


def read_metric(metric: dict, base: str, run: dict):
    """The metric's value through its reader, or None where the reader finds
    nothing to read. A metric split by the end-to-end metric it moves
    (``<name>.<variant>``) may share the file of its base name."""
    names = (metric["name"], metric["name"].split(".")[0])
    path = next(p for p in (os.path.join(d, "metrics", f"{n}.json")
                            for n in names for d in (base, BENCH))
                if os.path.exists(p))
    spec = load_json(path)
    reader = importlib.import_module(f"mxbench.readers.{spec['reader']}")
    if run["rehearsal"] and metric["source"] == "device_trace":
        return None
    value = reader.read(run, spec.get("args", {}))
    if value is None:
        return None
    return {"value": float(value), "unit": metric["unit"]}


def judge(numbers: dict, limits: dict):
    """`correct` from the numbers compared: each has a limit of its own in
    the cell's file and may not pass it. Keys that start with ``_`` are
    notes, printed and not held. No number at all is not correct."""
    notes = {k: v for k, v in numbers.items() if k.startswith("_")}
    compared, correct = {}, True
    for name, value in numbers.items():
        if name in notes:
            continue
        limit = limits[name]
        ok = value is not None and value == value and value <= limit
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit}
    return bool(correct and compared), compared, notes


def device_record(jax, devices):
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "backend": jax.default_backend(),
            "memory_peak_bytes": peak,
            "memory_stats": {k: int(v) for k, v in
                             (devices[0].memory_stats() or {}).items()
                             if isinstance(v, (int, float))}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="only for the cells under bench/tests/")
    ap.add_argument("--benchmark", default=None,
                    help="the benchmark file (default: BENCHMARK.json at the "
                         "root; the rehearsal's is bench/tests/BENCHMARK.json)")
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: write a summary of the trace, for "
                         "a person to look at, to this file")
    args = ap.parse_args(argv)

    base = os.path.join(BENCH, "tests") if args.rehearsal else BENCH
    bfile = args.benchmark or os.path.join(
        base if args.rehearsal else ROOT, "BENCHMARK.json")
    if not os.path.exists(bfile):
        raise Refuse(f"{bfile} is missing")
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        raise Refuse("the program (mxnet_tpu/) is not in this checkout")
    benchmark = load_json(bfile)
    cell, spec, cfg = find_cell(benchmark, args.workload, base)
    alias_package(base)

    # JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR names
    # one, there; else at a fixed path inside the checkout (the program sets
    # the same path when it is imported). Small programs are cached too: every
    # run is a new process, and what is not cached compiles in every set-up.
    # The rehearsal caches nothing: a CPU executable that XLA loads back from
    # the cache can fail ("Function ... not found"), and the program's own
    # tests share the checkout's cache directory.
    import jax
    if args.rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    chips = int(cell["chips"])
    peaks_table = load_json(os.path.join(BENCH, "peaks.json"))
    kind = devices[0].device_kind
    if args.rehearsal:
        if not spec.get("rehearsal_only"):
            raise Refuse("--rehearsal runs only the cells under bench/tests/")
        peaks = None
    else:
        if devices[0].platform != "tpu" or jax.default_backend() != "tpu":
            raise Refuse(f"no TPU: JAX's backend is "
                         f"{jax.default_backend()!r}")
        if kind not in peaks_table:
            raise Refuse(f"device kind {kind!r} is not in bench/peaks.json")
        peaks = peaks_table[kind]
    if len(devices) < chips:
        raise Refuse(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devices)}")
    devices = devices[:chips]
    note(jax=jax.__version__, backend=jax.default_backend(), device_kind=kind,
         device_count=len(jax.devices()), chips_used=chips,
         platform=devices[0].platform, rehearsal=args.rehearsal,
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         workload=cell["name"], seed=args.seed, seconds=args.seconds,
         trace=args.trace)

    from mxbench import reduce_trace
    traffic = importlib.import_module(
        f"mxbench.traffic.{spec['traffic']['kind']}")
    builder = importlib.import_module(f"mxbench.models.{cfg['builder']}")
    tracing = bool(args.trace) and not args.rehearsal
    seconds = float(args.seconds)
    if args.trace:
        # a trace of the whole of a long window would not be read back
        # inside a run's time limit: the traced run measures a shorter one
        seconds = min(seconds, float(spec.get("trace_window_s", 8.0)))

    ctx = {"cell": cell, "spec": spec, "cfg": cfg, "builder": builder,
           "seed": int(args.seed), "devices": devices, "chips": chips,
           "peaks": peaks, "note": note, "rehearsal": args.rehearsal,
           "sample_engine": bool(args.trace)}
    ctx["t_start"] = T_PROCESS_START
    state = traffic.setup(ctx)
    setup_s = time.perf_counter() - T_PROCESS_START

    trace_dir = None
    if tracing:
        trace_dir = os.path.join(ROOT, ".bench_trace",
                                 f"{cell['name']}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            facts = traffic.window(state, seconds)
    finally:
        t1 = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
    facts["setup_s"] = setup_s
    facts.setdefault("window_s", t1 - t0)
    facts = traffic.after_window(state, facts)

    device = device_record(jax, devices)
    traffic.release(state)
    gc.collect()

    trace = None
    if tracing:
        trace = reduce_trace.load_dir(trace_dir, chips, window_s=t1 - t0)
        if args.dump_trace:
            os.makedirs(os.path.dirname(args.dump_trace) or ".",
                        exist_ok=True)
            with open(args.dump_trace, "w") as f:
                json.dump(reduce_trace.summary(trace), f, indent=1)
            with open(args.dump_trace + ".sample.json", "w") as f:
                json.dump(reduce_trace.sample(
                    trace, float(spec.get("trace_sample_s", 0.5))), f)
        device["busy_s"] = reduce_trace.mean_busy_s(trace)
        device["window_s"] = reduce_trace.window_s(trace)

    run = {"facts": facts, "trace": trace, "cfg": cfg, "spec": spec,
           "cell": cell, "peaks": peaks, "chips": chips,
           "rehearsal": args.rehearsal}
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(benchmark, cell["name"], group):
        got = read_metric(m, base, run)
        if got is not None:
            metrics[m["name"]] = got

    t_check = time.perf_counter()
    numbers = traffic.check(state, ctx)
    facts["check_s"] = time.perf_counter() - t_check
    correct, compared, notes = judge(numbers, spec["limits"])

    result = {"correct": correct,
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]),
              "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = reduce_trace.breakdown(trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    extra = {k: facts[k] for k in spec.get("report_facts", []) if k in facts}
    if extra:
        result["facts"] = extra
    if notes:
        print(f"compared notes: {json.dumps(notes)}", file=sys.stderr)
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
