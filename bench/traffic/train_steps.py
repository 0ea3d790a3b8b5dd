"""Training traffic: one ``parallel.TrainStep`` fed a fresh batch every step.

The cell's ``traffic`` block gives the global batch, the sequence length and
how many distinct batches the feed cycles through; ``step`` gives
``TrainStep``'s ``block_every`` and ``optimizer`` Adam's. One chip: a cell
across chips brings its mesh with it. Tokens come from the seed, made on the device.

Set-up builds ONE step object, drives it through its first three steps by
the window's own call (``TrainStep.step``, a different batch each time) and
reads what `correct` compares: each step's loss, the norm of every leaf of
the first gradient (from Adam's first moment after step 1: m = (1-b1) g), and
the norm of every leaf's change after the three. Those three steps are also
the warm-up. The same object then runs the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from mxbench import flops
from mxbench.reference.common import seed_words

CHECK_STEPS = 3


def make_batches(cfg, seed, n, batch, seq):
    """``n`` batches of token ids ``[n, batch, seq + 1]`` from the seed, in
    one jitted call; inputs are ``[..., :-1]`` and labels ``[..., 1:]``."""
    vocab = int(cfg["vocab_size"])

    def draw(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(1), words[0]), words[1])
        return jax.random.randint(key, (n, batch, seq + 1), 0, vocab,
                                  jnp.int32)

    return jax.jit(draw)(np.asarray(seed_words(seed)))


def _norms_fn(builder, names):
    """Norms of the pieces `correct` compares, of arrays given in the order
    of ``names``."""
    @jax.jit
    def norms(arrays):
        out = []
        for name, a in zip(names, arrays):
            out += [jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
                    for _, _, p in builder.parts(name, a)]
        return jnp.stack(out)
    return norms


def setup(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.ndarray import NDArray

    spec, cfg, builder = ctx["spec"], ctx["cfg"], ctx["builder"]
    tr, st, opt = spec["traffic"], spec.get("step", {}), spec["optimizer"]
    B, T, n = int(tr["global_batch"]), int(tr["seq"]), int(tr["batches"])
    seed = ctx["seed"]

    net = builder.build_net(cfg, seed, train=True)
    tokens = make_batches(cfg, seed, n, B, T)
    optimizer = mx.optimizer.Adam(
        learning_rate=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
        epsilon=opt["epsilon"])
    step = parallel.TrainStep(
        net, SoftmaxCrossEntropyLoss(), optimizer,
        example_inputs=[NDArray(tokens[0, :, :-1])],
        block_every=int(st.get("block_every", 2)))
    ids = [NDArray(tokens[i, :, :-1]) for i in range(n)]
    labels = [NDArray(tokens[i, :, 1:]) for i in range(n)]

    # names of the step's slots, by the program's own parameter names
    by_id = {id(p): name for name, p in net.collect_params().items()}
    names = [by_id[id(p)] for p in step.model.params]
    slots = list(step.model.diff_slots)
    dnames = [names[s] for s in slots]
    norms = _norms_fn(builder, dnames)
    ctx["note"](phase="train_built", since_start_s=time.perf_counter()
                - ctx.get("t_start", time.perf_counter()))

    state = {"step": step, "net": net, "ids": ids, "labels": labels,
             "n": n, "B": B, "T": T, "fed": 0, "ctx": ctx}
    got = {"losses": []}
    for i in range(CHECK_STEPS):
        loss = feed(state)
        got["losses"].append(float(loss.item()))
        if i == 0:
            b1 = float(opt["beta1"])
            m = [jax.tree.leaves(step._opt_states[s])[0] for s in slots]
            got["grad_norms"] = np.asarray(norms(m)) / (1.0 - b1)
            del m
            ctx["note"](phase="train_first_step", since_start_s=(
                time.perf_counter() - ctx.get("t_start", 0)))
    got["delta_norms"] = np.asarray(delta_norms(
        builder, cfg, seed, names, slots, step.model.values()))
    state["got"] = got
    state["parts"] = [(leaf, layer) for n in dnames
                      for leaf, layer, _ in builder.parts(
                          n, np.zeros((3,), np.int8))]
    ctx["note"](phase="train_checked_steps", since_start_s=(
        time.perf_counter() - ctx.get("t_start", 0)))

    text = step.compiled().as_text()
    ctx["note"](phase="train_step_compiled",
                tpu_custom_call="tpu_custom_call" in text,
                tpu_custom_calls=text.count("tpu_custom_call"),
                losses_first_steps=got["losses"], launches={})
    del text
    return state


def delta_norms(builder, cfg, seed, names, slots, values):
    """Norm of every differentiable parameter's change from the seed's
    initial weights, which are made again inside the same program (so that
    no second copy of them outlives the call)."""
    key = builder.cfg_key(cfg)

    @jax.jit
    def fn(vals, words):
        tree = builder.ref.init_params(dict(key), (words[0], words[1]),
                                       builder.dtype_of(dict(key)))
        out = []
        for s in slots:
            leaf, layer, transposed = builder.leaf_of(names[s])
            w0 = tree[leaf] if layer is None else tree["layers"][leaf][layer]
            w0 = w0.T if transposed else w0
            d = vals[s].astype(jnp.float32) - w0.astype(jnp.float32)
            out += [jnp.sqrt(jnp.sum(jnp.square(p)))
                    for _, _, p in builder.parts(names[s], d)]
        return jnp.stack(out)

    return fn(tuple(values), np.asarray(seed_words(seed)))


def feed(state):
    """The window's call: one ``TrainStep.step`` on the next batch."""
    i = state["fed"] % state["n"]
    state["fed"] += 1
    return state["step"].step(state["ids"][i], state["labels"][i])


def window(state, seconds):
    step = state["step"]
    step.drain()
    steps = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.train.window"):
        while time.perf_counter() < end:
            with jax.profiler.TraceAnnotation("bench.train.step_call"):
                loss = feed(state)
            steps += 1
        with jax.profiler.TraceAnnotation("bench.train.drain"):
            step.drain()
            jax.block_until_ready(loss._data)
    t1 = time.perf_counter()
    state["last_loss"] = float(loss.item())
    return {"window_s": t1 - t0, "steps": steps,
            "tokens": steps * state["B"] * state["T"]}


def after_window(state, facts):
    ctx = state["ctx"]
    cfg, work = ctx["cfg"], ctx["builder"].work
    B, T = state["B"], state["T"]
    facts["attempted"] = facts["steps"]
    ok = np.isfinite(state["last_loss"])
    facts["failed"] = 0 if ok else facts["steps"]
    facts["last_loss"] = state["last_loss"]
    facts["model_flops"] = facts["tokens"] * work.train_flops_per_token(
        cfg, T)
    if ctx["peaks"]:
        heads, head_dim, layers = work.train_attention(cfg)
        fa = flops.flash_attention_train(B, heads, T, head_dim, ctx["peaks"])
        # every such layer of every step runs one forward and one backward
        # kernel
        facts["flash_least_s_per_device"] = (
            facts["steps"] * layers * fa["seconds"])
        facts["flash_binds"] = fa["binds"]
    return facts


def release(state):
    """Drop the program's state, so that the reference has the chip."""
    for k in ("step", "net", "ids", "labels"):
        state.pop(k, None)


def check(state, ctx):
    """Follow the same three steps in the reference and compare."""
    builder, cfg, spec = ctx["builder"], ctx["cfg"], ctx["spec"]
    tr, opt = spec["traffic"], spec["optimizer"]
    got = state["got"]
    tokens = make_batches(cfg, ctx["seed"], int(tr["batches"]),
                          int(tr["global_batch"]), int(tr["seq"]))
    batches = [(tokens[i % tokens.shape[0], :, :-1],
                tokens[i % tokens.shape[0], :, 1:])
               for i in range(CHECK_STEPS)]
    params = builder.reference_weights(cfg, ctx["seed"])
    want = reference_readings(builder, params, batches, cfg, opt, spec)
    return compare(got, want, state["parts"])


def reference_readings(builder, params, batches, cfg, opt, spec, fake=None,
                       transform=None):
    """The reference's losses and per-leaf norms. ``fake`` computes it in a
    lower precision (the control); ``transform`` maps the batches first (the
    faults planted for the calibration)."""
    if transform is not None:
        batches = [transform(b) for b in batches]
    store = builder.dtype_of(cfg) if spec.get("reference_stores_as_config",
                                              True) else None
    losses, g1, delta = builder.ref.train_steps(
        params, batches, cfg, opt, fake=fake,
        rows=int(spec.get("reference_rows", 2)), store_dtype=store)
    return {"losses": losses,
            "grad_norms": {k: np.asarray(v) for k, v in g1.items()},
            "delta_norms": {k: np.asarray(v) for k, v in delta.items()}}


def as_vector(norms: dict, parts):
    """The reference's per-leaf norms in the order of ``parts``."""
    return np.asarray([float(norms[leaf]) if layer is None
                       else float(norms[leaf][layer])
                       for leaf, layer in parts])


def as_got(readings: dict, parts):
    """The reference's readings in the shape of the program's (what puts
    the control, or a planted fault, in the program's place)."""
    return {"losses": readings["losses"],
            "grad_norms": as_vector(readings["grad_norms"], parts),
            "delta_norms": as_vector(readings["delta_norms"], parts)}


def compare(got, want, parts):
    """The numbers `correct` holds: the worst leaf's gap of norms for the
    first gradient and for the change over the three steps, each against
    the larger of that leaf's reference norm and the median leaf's. (Each
    step's relative loss gap is printed as a note and not held: the program
    returns its loss in bfloat16, whose rounding at 11 is 0.3 %, more than
    the control or any fault moves it — see PERF.md.) Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the change."""
    out = {"_loss_gaps": [abs(a - b) / abs(b) for a, b in
                          zip(got["losses"], want["losses"])]}

    names = [leaf if layer is None else f"{leaf}[{layer}]"
             for leaf, layer in parts]

    g_ref = as_vector(want["grad_norms"], parts)
    d_ref = as_vector(want["delta_norms"], parts)
    g_got, d_got = np.asarray(got["grad_norms"]), np.asarray(
        got["delta_norms"])
    g_med, d_med = float(np.median(g_ref)), float(np.median(d_ref))
    g_gap = np.abs(g_got - g_ref) / np.maximum(g_ref, g_med)
    counted = g_ref >= 1e-3 * g_med
    d_gap = np.abs(d_got - d_ref) / np.maximum(d_ref, d_med)
    out["grad_norm"] = float(np.max(g_gap))
    out["update_norm"] = float(np.max(d_gap[counted]))
    top = np.argsort(-np.where(counted, d_gap, -1))[:4]
    out["_worst"] = {"grad_leaf": names[int(np.argmax(g_gap))],
                     "update_leaves": [[names[int(i)], float(d_gap[i])]
                                       for i in top],
                     "update_median_gap": float(np.median(d_gap[counted])),
                     "left_out": int((~counted).sum())}
    return out
