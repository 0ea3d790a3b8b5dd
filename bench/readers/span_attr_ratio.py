"""The sum of one attribute over the sum of another, in %, over the program's
spans named in ``spans`` that lie inside the traced window
(``mxbench.program_trace``): ``over`` and ``under`` name the attributes
(``sel`` / ``of``: blocks of a sparse layer's cache a dispatched program
reads, of the blocks its rows hold). The engine sums the same two numbers in
``stats()``; the harness keeps no fact of them, so the traced window's spans
are where a reader finds them. Spans without both attributes (a program that
selects nothing, a commit before the attributes) are passed over; none left
-> nothing, never 0."""
from mxbench import program_trace, reduce_trace


def read(run, args):
    record = program_trace.load(run)
    if record is None or not record["threads"]:
        return None
    window = reduce_trace.bounds(run["trace"])
    over = under = 0.0
    for name in args["spans"]:
        for _, span in program_trace.spans_named(record, name):
            attrs = span[2]
            if program_trace.inside(span, window) and \
                    args["over"] in attrs and args["under"] in attrs:
                over += float(attrs[args["over"]])
                under += float(attrs[args["under"]])
    if under <= 0.0:
        return None
    return 100.0 * over / under
