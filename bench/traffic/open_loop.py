"""Open-loop serving traffic: requests are sent on a schedule whether or not
earlier ones have finished, at the rate fixed in the cell (found once by a
sweep; never searched for here). Every latency is timed from the instant the
request was *due*, so a stall charges the requests behind it; how late the
generator itself ran is reported beside them.

The window is the ``--seconds`` in which requests fall due. After it closes
the run waits, up to a minute, for every request that was due: one that
comes late is late, not wrong, and its latency counts the wait.
"""
from __future__ import annotations

import time

import jax

from mxbench.traffic import serve_common as sc

setup = sc.setup_engine
release = sc.release
check = sc.check


def window(state, seconds):
    from mxnet_tpu.serve.engine import QueueFullError
    ctx = state["ctx"]
    tr = ctx["spec"]["traffic"]
    engine = state["engine"]
    n = max(1, int(round(float(tr["arrivals"]["rate_per_s"]) * seconds)))
    reqs = sc.make_requests(tr, ctx["cfg"], ctx["seed"], n)
    due = sc.arrival_offsets(tr, n, seconds)
    sampler = sc.OccupancySampler(engine) if ctx.get("sample_engine") else None
    records = []
    if sampler:
        sampler.start()
    t0 = time.perf_counter()
    for req, d in zip(reqs, due):
        with jax.profiler.TraceAnnotation("bench.serve.wait_for_due"):
            delay = t0 + d - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        rec = {"req": req, "due": float(d), "refused": False,
               "sent": time.perf_counter() - t0}
        with jax.profiler.TraceAnnotation("bench.serve.submit"):
            try:
                rec["handle"], rec["feed"] = sc.submit(engine, req)
            except QueueFullError:
                rec["refused"] = True
        records.append(rec)
    with jax.profiler.TraceAnnotation("bench.serve.window_tail"):
        delay = t0 + seconds - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
    t_close = time.perf_counter()
    if sampler:
        sampler.stop()
        state["occupancy"] = sampler.samples
    state.update(records=records, t0=t0, t_close=t_close, seconds=seconds)
    return {"window_s": t_close - t0}


def after_window(state, facts):
    deadline = state["t_close"] + 60.0
    for rec in state["records"]:
        if rec["refused"]:
            continue
        left = deadline - time.perf_counter()
        rec["handle"]._event.wait(max(left, 0.0))
    got = sc.window_facts(state, state.pop("records"), state["t0"],
                          state["t_close"], facts["window_s"])
    got["setup_s"] = facts["setup_s"]
    got["drain_s"] = time.perf_counter() - state["t_close"]
    return sc.pick_sample(state, sc.occupancy_facts(state, got))
