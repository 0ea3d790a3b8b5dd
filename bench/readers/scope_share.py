"""The share, in %, of the device time of the executed programs whose name
holds one of ``modules`` that was spent in operations under the program's
scope ``scope`` (a ``jax.named_scope`` such as ``mx.paged_attention``; a list
means any of them): the summed durations of the ``XLA Ops`` events inside
those programs whose instruction's ``op_name`` path holds the scope (read from
the optimised modules in the trace's ``/host:metadata`` plane, see
``mxbench.program_trace``; a backward operation keeps the name inside
``transpose(jvp(...))``), over the programs' summed durations on the ``XLA
Modules`` line. Gaps inside a program count against every scope.

An instruction the compiler made has no scope of its own and counts under its
nearest neighbour's (``program_trace.hlo_scopes``). With ``"unnamed": true``
in place of ``scope`` the reader gives the share of exactly those operations,
inherited or under no scope at all: how much of the other shares of the same
programs is the heuristic's and not the program's own naming.

Finds nothing (no trace, no such program, no such scope: a commit before
PR 26, or an executable loaded from a cache that an older program filled) ->
nothing, never 0."""
from mxbench import program_trace


def read(run, args):
    record = program_trace.load(run)
    if record is None:
        return None
    got = program_trace.scope_seconds(record, run["trace"], args["modules"])
    if got is None:
        return None
    total, by_path = got
    if args.get("unnamed"):
        if not any(program_trace.own_scope(path) for path in by_path):
            return None             # a program without scopes: not 100 %
        under = sum(s for path, s in by_path.items()
                    if not program_trace.own_scope(path))
        return 100.0 * under / total
    scopes = args["scope"]
    if isinstance(scopes, str):
        scopes = [scopes]
    under = sum(s for path, s in by_path.items()
                if any(scope in path for scope in scopes))
    if under <= 0.0:
        return None
    return 100.0 * under / total
