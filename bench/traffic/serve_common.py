"""What the two serving generators share: the engine's set-up, the request
mix, the stamped token feed, the facts of a window and the check.

A mix is data (the cell's ``traffic`` block): length distributions, sampling
settings, and for an open loop the rate of its Poisson arrivals. Sizes, gaps
and their order are drawn from the mix's own fixed ``mix_seed``: the mix is
one timeline, replayed. The run's ``--seed`` draws
the token ids, the sampling seeds and the weights. (Reordering by the seed
was tried first: on this engine the order alone moved TTFT p90 by +-30 % and
the median token gap between 88 and 150 ms, while two runs of one order
agree within 1-3 % — PERF.md, Findings.)
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from mxbench import flops


# --------------------------------------------------------------------- mix
def _draw_lengths(rng, dist: dict, n: int):
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = np.exp(np.log(float(dist["median"]))
                   + float(dist["sigma"]) * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x).astype(np.int64), lo, hi)


def make_sizes(tr: dict, n: int):
    """``n`` (prompt, output, greedy?) rows from the mix's fixed seed."""
    rng = np.random.RandomState(int(tr.get("mix_seed", 0)))
    prompts = _draw_lengths(rng, tr["prompt"], n)
    outputs = _draw_lengths(rng, tr["output"], n)
    cap = int(tr["max_total"])
    over = prompts + outputs > cap
    prompts[over] = np.maximum(int(tr["prompt"]["min"]),
                               cap - outputs[over])
    samp = tr.get("sampling", {})
    share = float(samp.get("greedy_share", 1.0))
    greedy = np.zeros(n, bool)
    if share >= 1.0 or float(samp.get("temperature", 0.0)) == 0.0:
        greedy[:] = True
    elif share > 0:
        greedy[rng.permutation(n)[:max(1, int(round(share * n)))]] = True
    return prompts, outputs, greedy


def make_requests(tr: dict, cfg: dict, seed: int, n: int):
    """The mix's ``n`` requests in the mix's order, token ids and sampling
    seeds drawn from ``seed``."""
    prompts, outputs, greedy = make_sizes(tr, n)
    rng = np.random.RandomState(seed % (2 ** 32))
    vocab = int(cfg["vocab_size"])
    samp = tr.get("sampling", {})
    reqs = []
    for i in range(n):
        reqs.append({
            "prompt": rng.randint(0, vocab, int(prompts[i])).astype(np.int32),
            "max_new": int(outputs[i]),
            "greedy": bool(greedy[i]),
            "temperature": 0.0 if greedy[i] else float(
                samp.get("temperature", 0.0)),
            "top_p": 1.0 if greedy[i] else float(samp.get("top_p", 1.0)),
            "seed": int(rng.randint(0, 2 ** 31 - 1)),
        })
    return reqs


def arrival_offsets(tr: dict, n: int, seconds: float):
    """``n`` due times inside ``(0, seconds)``: the gaps of a Poisson process
    drawn from the mix's fixed seed and scaled to fill the window."""
    rng = np.random.RandomState(int(tr.get("mix_seed", 0)) + 1)
    gaps = rng.exponential(1.0, n + 1)
    return (np.cumsum(gaps)[:n] / gaps.sum()) * seconds


# ------------------------------------------------------------ token stamps
class StampQueue(queue.Queue):
    """The request's token feed (``submit(stream=True)``), stamping each
    token as it is handed to the client."""

    def __init__(self):
        super().__init__()
        self.stamps = []

    def put(self, item, block=True, timeout=None):
        if item[0] == "token":
            self.stamps.append(time.perf_counter())
        super().put(item, block, timeout)


def submit(engine, req):
    h = engine.submit(req["prompt"], req["max_new"], eos_token_id=None,
                      temperature=req["temperature"], top_p=req["top_p"],
                      seed=req["seed"], stream=True)
    feed = StampQueue()
    h._events = feed
    return h, feed


# ------------------------------------------------------------------ set-up
def setup_engine(ctx):
    from mxnet_tpu import metrics
    from mxnet_tpu.serve import InferenceEngine

    spec, cfg, builder = ctx["spec"], ctx["cfg"], ctx["builder"]
    metrics.enable()
    dev = ctx["devices"][0]
    in_use = lambda: int((dev.memory_stats() or {}).get("bytes_in_use", 0))
    since = lambda: time.perf_counter() - ctx.get("t_start", 0)
    net = builder.build_net(cfg, ctx["seed"], train=False)
    ctx["note"](phase="serve_net_built", bytes_in_use=in_use(),
                since_start_s=since())
    engine = InferenceEngine(net, **spec["engine"])
    ctx["note"](phase="serve_engine_built", bytes_in_use=in_use(),
                since_start_s=since())
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    engine.start()
    # two requests through the whole path: one greedy, one sampled
    rng = np.random.RandomState(12345)
    vocab = int(cfg["vocab_size"])
    warm = []
    for temp in (0.0, 0.7):
        warm.append(engine.submit(
            rng.randint(0, vocab, 40).astype(np.int32), 4, temperature=temp,
            top_p=1.0 if temp == 0.0 else 0.95, seed=1, stream=True))
    for h in warm:
        r = h.result(timeout=600)
        if not r.ok:
            raise SystemExit(f"bench: warm-up request ended {r.status}: "
                             f"{r.error}")
    stats = engine.stats()
    tally = {lv[0]: int(child.value)
             for lv, child in metrics.DECODE_LAUNCHES.children()
             if child.value}
    ctx["note"](phase="serve_ready", paged=bool(stats["paged"]),
                page_size=stats.get("page_size"), slots=stats["slots"],
                kv_bytes=stats["kv_bytes"], warmup_s=warm_s,
                bytes_in_use=in_use(), since_start_s=since(),
                compiled_buckets=stats["compiled_buckets"],
                launches=tally)
    return {"engine": engine, "net": net, "ctx": ctx,
            "preempt0": int(stats.get("preemptions", 0))}


class OccupancySampler(threading.Thread):
    """Slots in use, read from ``engine.stats()`` at a fixed interval."""

    def __init__(self, engine, interval=0.1):
        super().__init__(daemon=True)
        self.engine, self.interval = engine, interval
        self.samples, self._stop_evt = [], threading.Event()

    def run(self):
        while not self._stop_evt.wait(self.interval):
            s = self.engine.stats()
            self.samples.append((s["slots_in_use"], s["queue_depth"]))

    def stop(self):
        self._stop_evt.set()
        self.join(timeout=5)


# ------------------------------------------------------------------- facts
def window_facts(state, records, t0, t_close, seconds):
    """Everything the readers want, from the records of one window. A record
    is {"req", "due", "sent", "handle", "feed", "refused"}; ``due`` and
    ``sent`` are offsets from ``t0``."""
    ctx = state["ctx"]
    cfg, work = ctx["cfg"], ctx["builder"].work
    chunk = int(ctx["spec"]["engine"].get("prefill_chunk")
                or state["engine"].stats()["page_size"])
    ttft, itl, qwait, late = [], [], [], []
    tokens = prompt_tokens = 0
    failed = 0
    decode_kv_bytes = decode_tokens = 0
    model_flops = 0.0
    chunks = []
    done = []
    for rec in records:
        late.append((rec["sent"] - rec["due"]) * 1e3)
        if rec["refused"]:
            failed += 1
            ttft.append(float("inf"))
            continue
        h, feed = rec["handle"], rec["feed"]
        res = h._result if h.done() else None
        stamps = list(feed.stamps)
        P = len(rec["req"]["prompt"])
        if stamps:
            ttft.append((stamps[0] - (t0 + rec["due"])) * 1e3)
            itl.extend((b - a) * 1e3 for a, b in zip(stamps, stamps[1:]))
            if stamps[0] <= t_close:
                prompt_tokens += P
                model_flops += work.forward_flops(cfg, P, 0)
                chunks.extend((min(chunk, P - s), s)
                              for s in range(0, P, chunk))
            for j, t in enumerate(stamps):
                if t <= t_close:
                    tokens += 1
                    if j >= 1:
                        decode_tokens += 1
                        decode_kv_bytes += work.cache_bytes(cfg, P + j)
                        model_flops += work.forward_flops(cfg, 1, P + j - 1)
        else:
            ttft.append(float("inf"))
        if res is None or rec.get("cancelled"):
            if res is None and not rec.get("cancelled"):
                failed += 1          # never finished, a minute past close
            continue
        if not res.ok:
            failed += 1
            continue
        if res.queue_wait_s is not None:
            qwait.append(res.queue_wait_s * 1e3)
        done.append((rec, res))
    facts = {
        "attempted": sum(1 for r in records if not r.get("cancelled")),
        "failed": failed, "window_s": seconds,
        "ttft_ms": ttft, "itl_ms": itl, "queue_wait_ms": qwait,
        "tokens": tokens + prompt_tokens, "generated_tokens": tokens,
        "prompt_tokens": prompt_tokens,
        "requests_done": len(done),
        "generator_late_p50_ms": float(np.median(late)) if late else 0.0,
        "generator_late_max_ms": float(np.max(late)) if late else 0.0,
        "decode_tokens": decode_tokens,
        "decode_kv_bytes": decode_kv_bytes,
        # what any decode step reads at the least: a pass that holds one row
        "weight_bytes": work.weight_bytes(cfg, 1),
        "model_flops": model_flops,
    }
    fin = sorted(x for x in ttft if np.isfinite(x))
    for q in (50, 75, 90):
        facts[f"ttft_p{q}_ms"] = (float(np.percentile(fin, q))
                                  if fin else None)
    for q in (50, 95):
        facts[f"itl_p{q}_ms"] = float(np.percentile(itl, q)) if itl else None
    if ctx["peaks"]:
        pl = flops.prefill_least_s(work, cfg, chunks, ctx["peaks"])
        facts["prefill_least_s"] = pl["seconds"]
        facts["prefill_binds"] = pl["binds"]
    stats = state["engine"].stats()
    facts["preemptions"] = int(stats.get("preemptions", 0)) \
        - state["preempt0"]
    facts["slots"] = int(stats["slots"])
    state["done"] = done
    return facts


def occupancy_facts(state, facts):
    """What the sampler saw, where it ran (the traced run)."""
    occ = state.pop("occupancy", None)
    if occ:
        facts["slots_in_use_mean"] = sum(s for s, _ in occ) / len(occ)
        # the queue about the window's middle and at its end: each the mean
        # over a tenth of the window, since one sample catches one arrival
        n, w = len(occ), max(1, len(occ) // 10)
        mid = occ[n // 2 - w // 2:n // 2 - w // 2 + w]
        facts["queue_depth_end_over_mid"] = [
            sum(q for _, q in mid) / len(mid),
            sum(q for _, q in occ[-w:]) / w]
    return facts


def pick_sample(state, facts):
    """The requests the reference will follow: greedy ones that finished,
    the longest of them and others drawn from the seed, ``check_requests``
    at the most (all of them, where fewer finished)."""
    ctx = state["ctx"]
    k = int(ctx["spec"]["check_requests"])
    greedy = [(rec, res) for rec, res in state.pop("done")
              if rec["req"]["greedy"]]
    mismatch = sum(1 for rec, res in greedy
                   if len(res.generated_ids) != rec["req"]["max_new"])
    total = lambda rr: len(rr[0]["req"]["prompt"]) + len(rr[1].generated_ids)
    greedy.sort(key=total, reverse=True)
    rng = np.random.RandomState((ctx["seed"] + 99) % (2 ** 32))
    picked = greedy[:1]
    rest = greedy[1:]
    for i in rng.permutation(len(rest))[:max(0, k - 1)]:
        picked.append(rest[i])
    state["sample"] = [
        {"prompt": [int(t) for t in rec["req"]["prompt"]],
         "generated": [int(t) for t in res.generated_ids]}
        for rec, res in picked]
    state["length_mismatch"] = mismatch
    facts["checked_requests"] = len(picked)
    facts["checked_tokens"] = sum(len(s["generated"])
                                  for s in state["sample"])
    return facts


def release(state):
    engine = state.pop("engine", None)
    if engine is not None:
        engine.shutdown(drain=False, timeout=60)
    state.pop("net", None)


def check(state, ctx):
    """The reference, once the window has closed, over the sample."""
    sample = state.get("sample") or []
    if not sample:
        return {}
    return compare(ctx["builder"], ctx["cfg"], ctx["spec"], ctx["seed"],
                   sample, state["length_mismatch"])


def compare(builder, cfg, spec, seed, sample, mismatch, fake=None):
    """Run the reference once over each sampled prompt with its served
    tokens: the widest gap by which a served (greedy) token's reference
    logit lies below the reference's best (one wrong token shows here); the
    mean of those gaps over every token compared (precision lost everywhere
    shows here, and it is steadier than a maximum); and, exactly, how many
    finished requests came back with another number of tokens than was
    asked. With
    ``fake`` the token judged at each position is the one the reference
    computed in that lower precision puts first (the control)."""
    params = builder.reference_weights(cfg, seed)
    seqs = [s["prompt"] + s["generated"] for s in sample]
    gaps = builder.ref.served_gaps(
        params, seqs, [len(s["prompt"]) for s in sample], cfg, fake=fake,
        pad_to=int(spec.get("reference_pad_to",
                            builder.work.max_positions(cfg))))
    flat = np.asarray([g for row in gaps for g in row])
    return {"logit_gap": float(flat.max()),
            "gap_mean": float(flat.mean()),
            "length_mismatch": float(mismatch),
            "_gaps": {"requests": len(sample), "tokens": int(flat.size),
                      "not_best_share": float(np.mean(flat > 0)),
                      "p99": float(np.percentile(flat, 99))}}
