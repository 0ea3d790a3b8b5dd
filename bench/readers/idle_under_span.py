"""The share of the traced window, in %, in which the device was idle while
a span called ``span`` was open on some thread of the program: the device's
idle gaps inside the window (from ``run["trace"]``, the device and the window
that ``device_idle`` reads) cut with the union of those spans
(``mxbench.program_trace``). With ``mx.serve.idle`` it is the idle time in
which the engine had no request to work on; with ``mx.serve.tick`` the idle
time in which it had one and the host had not yet given the chip its next
program. The two and what lies under no span add up to
``device_idle_share``. No trace, or a program without ``mx.*`` spans ->
nothing; spans of other names only -> 0."""
from mxbench import program_trace, reduce_trace


def read(run, args):
    record = program_trace.load(run)
    if record is None or not record["threads"]:
        return None
    trace = run["trace"]
    window = reduce_trace.window_s(trace)
    if not window or not trace["devices"]:
        return None
    spans = [(sp[0], sp[1]) for _, sp in program_trace.spans_named(
        record, args["span"])]
    return 100.0 * program_trace.idle_inside_s(trace, spans) / window
