"""A statistic over the program's spans called ``span`` that lie inside the
traced window (``mxbench.program_trace``: the ``mx.*`` TraceMe annotations
that ``mxnet_tpu.profiler.scope`` opens), any thread. ``stat`` is ``count``
(0 is a reading: the program has spans, none of this name), ``pN`` (N-th
percentile of the durations) or ``self_pN`` (of each span's duration
less what the spans nested in it on its own thread cover; ``children``
narrows which nested spans count, so that ``self`` can mean "the tick less
its two waits for the device"). ``where`` filters on the span's attributes
(``{"rows_min": 1}``: ticks that decoded at least one row). Durations are
reported in ms, a count as it is. No trace, or a program without ``mx.*``
spans (a commit before PR 26) -> nothing."""
from mxbench import program_trace, reduce_trace


def read(run, args):
    record = program_trace.load(run)
    if record is None or not record["threads"]:
        return None
    window = reduce_trace.bounds(run["trace"])
    where = args.get("where", {})
    picked = [(t, sp) for t, sp in program_trace.spans_named(
        record, args["span"])
        if program_trace.inside(sp, window)
        and program_trace.matches(sp[2], where)]
    stat = args["stat"]
    if stat == "count":
        return len(picked)
    if not picked:
        return None
    if stat.startswith("self_"):
        stat = stat[len("self_"):]
        values = [program_trace.self_s(record, t, sp, args.get("children"))
                  for t, sp in picked]
    else:
        values = [sp[1] for _, sp in picked]
    return 1e3 * program_trace.quantile(values, float(stat[1:]))
