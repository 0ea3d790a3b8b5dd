"""Closed-loop serving traffic: a fixed number of clients, each sending its
next request when the last one completes, so the offered load follows the
system's own pace. What is judged is the work done per second: prompt tokens
whose prefill completed in the window plus tokens generated in it, over the
window's seconds. Requests still in flight when the window closes are
cancelled; what they had produced inside the window counts as work, and they
count neither as attempted nor as failed.
"""
from __future__ import annotations

import threading
import time

import jax

from mxbench.traffic import serve_common as sc

setup = sc.setup_engine
release = sc.release
check = sc.check


def _client(engine, reqs, t0, stop, records, lock):
    for req in reqs:
        if stop.is_set():
            return
        rec = {"req": req, "refused": False,
               "sent": time.perf_counter() - t0}
        rec["due"] = rec["sent"]
        try:
            rec["handle"], rec["feed"] = sc.submit(engine, req)
        except Exception:
            rec["refused"] = True
            with lock:
                records.append(rec)
            time.sleep(0.05)
            continue
        with lock:
            records.append(rec)
        while not rec["handle"]._event.wait(0.25):
            if stop.is_set():
                rec["cancelled"] = rec["handle"].cancel()
                rec["handle"]._event.wait(30.0)
                return


def window(state, seconds):
    ctx = state["ctx"]
    tr = ctx["spec"]["traffic"]
    engine = state["engine"]
    clients = int(tr["clients"])
    per_client = int(tr["requests_per_client"])
    reqs = sc.make_requests(tr, ctx["cfg"], ctx["seed"],
                            clients * per_client)
    sampler = sc.OccupancySampler(engine) if ctx.get("sample_engine") else None
    records, lock, stop = [], threading.Lock(), threading.Event()
    t0 = time.perf_counter()
    threads = [threading.Thread(
        target=_client, daemon=True,
        args=(engine, reqs[i::clients], t0, stop, records, lock))
        for i in range(clients)]
    if sampler:
        sampler.start()
    for t in threads:
        t.start()
    with jax.profiler.TraceAnnotation("bench.serve.clients_running"):
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    stop.set()
    if sampler:
        sampler.stop()
        state["occupancy"] = sampler.samples
    state.update(records=records, t0=t0, t_close=t_close, threads=threads)
    return {"window_s": t_close - t0}


def after_window(state, facts):
    for t in state.pop("threads"):
        t.join(timeout=90.0)
    got = sc.window_facts(state, state.pop("records"), state["t0"],
                          state["t_close"], facts["window_s"])
    got["setup_s"] = facts["setup_s"]
    return sc.pick_sample(state, sc.occupancy_facts(state, got))
