"""A kernel's share of its roofline over the prefill programs of the traced
window, in %: the least time the chip could take for the kernel's work (the
family's own per-kernel count, ``builder.work.kernel_count(cfg, kernel, n,
start)``: operations and bytes of ``kernel`` in all its layers for ``n`` new
positions of one row from depth ``start``; the larger of operations over the
peak and bytes over the HBM's) over the device time spent under the kernel's
scope (``scope``, a ``jax.named_scope``) in the programs whose name holds one
of ``modules``.

Which positions the traced programs computed is read from the program's own
spans: every ``span`` (``mx.serve.prefill_dispatch``) inside the window
carries the ``start`` and ``end`` of the chunk it dispatched. A family may
carry no per-kernel count; then, or without a trace, without such spans or
without the scope in the programs, there is nothing to read: nothing, never
0."""
import importlib

from mxbench import program_trace, reduce_trace


def read(run, args):
    record = program_trace.load(run)
    peaks = run["peaks"]
    if record is None or not peaks or not record["threads"]:
        return None
    work = importlib.import_module(
        f"mxbench.models.{run['cfg']['builder']}").work
    count = getattr(work, "kernel_count", None)
    if count is None:
        return None
    window = reduce_trace.bounds(run["trace"])
    least = 0.0
    for _, span in program_trace.spans_named(record, args["span"]):
        attrs = span[2]
        if not program_trace.inside(span, window) or "end" not in attrs:
            continue
        start, end = int(attrs["start"]), int(attrs["end"])
        flops, nbytes = count(run["cfg"], args["kernel"], end - start, start)
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    got = program_trace.scope_seconds(record, run["trace"], args["modules"])
    if got is None or least <= 0.0:
        return None
    under = sum(s for path, s in got[1].items() if args["scope"] in path)
    if under <= 0.0:
        return None
    return 100.0 * least / under
