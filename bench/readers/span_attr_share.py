"""The share, in %, of the program's spans named in ``spans`` that lie inside
the traced window (``mxbench.program_trace``) whose attribute ``attr`` is
nonzero: ``idle`` on ``mx.serve.prefill_dispatch`` and
``mx.serve.decode_dispatch`` is 1 where the dispatch found every program the
engine had sent already finished, so that the chip sat idle until this one
arrived. Spans without the attribute (a program that does not set it, a
commit before it) are passed over; none left -> nothing, never 0."""
from mxbench import program_trace, reduce_trace


def read(run, args):
    record = program_trace.load(run)
    if record is None or not record["threads"]:
        return None
    window = reduce_trace.bounds(run["trace"])
    attr = args["attr"]
    counted = flagged = 0
    for name in args["spans"]:
        for _, span in program_trace.spans_named(record, name):
            attrs = span[2]
            if attr in attrs and program_trace.inside(span, window):
                counted += 1
                flagged += float(attrs[attr]) != 0.0
    if not counted:
        return None
    return 100.0 * flagged / counted
