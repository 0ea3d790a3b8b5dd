"""TrainStep: one fully-fused, sharded XLA training step for a Gluon model.

This is where the TPU design beats the reference's execution model: the
reference runs forward op-by-op through the engine, a backward graph through
the engine again, then one fused optimizer op per parameter plus kvstore
push/pull per gradient. Here forward + backward + optimizer + collectives
compile into ONE executable; parameters and optimizer state are donated
(updated in place in HBM); gradient reduction is a GSPMD-inserted all-reduce
over the 'dp' mesh axis.

Usage::

    mesh = parallel.make_mesh({'dp': 8})
    step = parallel.TrainStep(net, loss_fn, optimizer, mesh=mesh,
                              data_spec=P('dp'), label_spec=P('dp'))
    loss = step(x, y)          # params update in place
"""
from __future__ import annotations

import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import optimizer as opt_mod
from ..base import MXNetError, logger
from .. import metrics as _metrics
from .. import profiler as _profiler
from ..kvstore import quant as _quant
from ..ndarray import NDArray
from ..observability import health as _health
from ..observability import perf as _perf
from ..observability import trace as _trace
from . import elastic as _elastic
from . import mesh as _mesh_mod
from .functional import FunctionalModel, functionalize

__all__ = ["TrainStep"]


class TrainStep:
    def __init__(self, net, loss_fn, optimizer, example_inputs: Sequence,
                 example_labels=None, mesh: Optional[Mesh] = None,
                 data_spec=None, label_spec=None, donate: bool = True,
                 loss_has_aux: bool = False, remat: bool = False,
                 block_every: Optional[int] = None, zero: int = 0,
                 compression_params: Optional[dict] = None,
                 health: bool = False, health_config=None):
        """``remat=True`` rematerializes the forward during backward
        (``jax.checkpoint`` over the whole apply): activations are not
        stored, trading ~1 extra forward of FLOPs for O(layers) less HBM —
        the standard long-context / big-batch enabler.

        ``block_every=W`` bounds the dispatch run-ahead of :meth:`step`:
        up to W dispatched-but-unforced losses stay in flight; the W+1-th
        ``step()`` blocks on the oldest. ``None`` leaves :meth:`step`
        unbounded (PJRT's own queue is the only backpressure) — pick a
        small W (2-8) on real TPUs so the host cannot run minutes ahead
        of the device.

        ``zero=1|2`` shards the WEIGHT UPDATE over the 'dp' mesh axis
        (arXiv:2004.13336): optimizer state lives as a flat dp-sharded
        array (each replica holds 1/dp of every moment buffer), gradients
        reduce onto the shards (zero=1: all-reduce then slice — the
        classic optimizer-state-only partition; zero=2: a direct
        reduce-scatter, so a full gradient never materializes per
        replica), the update runs on the shard, and fresh params
        all-gather back to their annotated shardings. Requires a mesh
        with a 'dp' axis and an elementwise optimizer (norm-based rules —
        LARS/LAMB — need full-tensor norms and are rejected).

        ``compression_params={'type': 'int8'|'4bit', 'block': 128}``
        (zero mode only) quantizes the param all-gather: each replica
        ships block-scaled codes + fp32 scales instead of fp32 deltas
        (~3.9x / ~7.5x fewer wire bytes) with a per-shard error-feedback
        residual carried in the optimizer state, so the dropped precision
        re-enters the next step's update instead of being lost.

        ``health=True`` fuses the mxhealth reductions into the SAME
        step executable (observability/health): a fixed-shape fp32
        vector — nonfinite counts for grads/pre-update params/loss,
        global grad/update/param L2 norms — is returned beside the
        loss and read on the lazy-loss window's deferred schedule, so
        health adds no extra executable, no new host sync and no
        steady-state recompile. The attached :class:`HealthMonitor`
        (``self.health``; knobs via ``health_config`` — a
        :class:`~mxnet_tpu.observability.health.HealthConfig` or
        kwargs dict) classifies anomalies, dumps the flight recorder
        (``reason=numeric_anomaly``) and applies ``on_anomaly``:
        ``"skip"`` additionally compiles an on-device select that
        drops a nonfinite step's whole state transition bitwise (the
        AMP scaler's skip semantics); ``"halt"`` raises after the
        dump. ``health_config.sample_every=N`` samples per-layer-group
        max-abs/RMS every N steps through one separate cached
        executable (the only non-deferred read in the subsystem)."""
        self.net = net
        self.loss_fn = loss_fn
        self.remat = remat
        self.optimizer = optimizer if isinstance(optimizer, opt_mod.Optimizer) \
            else opt_mod.create(optimizer)
        example_inputs = [x if isinstance(x, NDArray) else NDArray(x)
                          for x in example_inputs]
        self.model: FunctionalModel = functionalize(net, *example_inputs,
                                                    training=True)
        self.mesh = mesh
        self.data_spec = data_spec
        self.label_spec = label_spec
        self._step = 0
        self._last_avals = None
        self._last_batch_sig = None
        self._seen_batch_sigs = set()
        self.zero = int(zero or 0)
        if self.zero not in (0, 1, 2):
            raise MXNetError(f"zero must be 0, 1 or 2, got {zero}")
        self._dp = 1
        self._compression = None
        if self.zero:
            if mesh is None or "dp" not in mesh.shape:
                raise MXNetError(
                    "zero=1|2 shards the weight update over the 'dp' mesh "
                    "axis; pass a mesh with a 'dp' axis")
            if not self.optimizer.lazy_rowwise:
                raise MXNetError(
                    f"zero={self.zero} needs an elementwise optimizer; "
                    f"{type(self.optimizer).__name__} takes full-tensor "
                    "norms and cannot update a 1/dp shard")
            self._dp = int(dict(mesh.shape)["dp"])
            if compression_params:
                # BlockQuantCompression owns the codec vocabulary and the
                # type/block validation; the traced step only needs the
                # (bits, block) pair
                from ..kvstore import BlockQuantCompression
                params = dict(compression_params)
                ctype = params.pop("type", "int8")
                block = params.pop("block", None)
                if params:
                    raise MXNetError(
                        f"unknown compression_params {sorted(params)}")
                comp = BlockQuantCompression(ctype, block=block)
                self._compression = (comp.bits, comp.block)
        elif compression_params:
            raise MXNetError("compression_params on TrainStep quantize the "
                             "ZeRO param all-gather; set zero=1|2 (the "
                             "kvstore owns non-ZeRO gradient compression)")
        #: diff slot -> (n, n_pad, chunk, block_eff) flat shard layout
        self._zero_meta = {}
        self._opt_states = [self._init_state(i, p)
                            for i, p in enumerate(self.model.params)]
        self._multi_cache = {}
        self._donate = donate
        if block_every is not None and block_every < 1:
            raise MXNetError(f"block_every must be >= 1, got {block_every}")
        self.block_every = block_every
        # with no window, retain only the most recent dispatches (drop-
        # without-block is safe: per-device execution is dispatch-ordered,
        # so draining a later loss implies the dropped earlier ones ran) —
        # an unbounded deque would pin every loss of a long run. With a
        # window, step() itself pops+blocks to keep len <= W (a maxlen
        # there would silently drop instead of applying backpressure).
        self._inflight: "deque" = deque(
            maxlen=None if block_every else 8)
        # (batch_sig, steps) -> executable: the jitted fn when the AOT
        # cache is off, a disk-restored/persisted executable when on
        self._aot_execs = {}
        #: mxhealth: HealthMonitor when health=True, else None. The
        #: health flag is a CONSTRUCTOR property (it changes the step
        #: program), so the jitted signature stays static and steady
        #: state stays recompile-free.
        self._health_on = bool(health)
        self.health = _health.HealthMonitor(health_config) \
            if self._health_on else None
        # deferred (step, device-vector) handles awaiting their lazy-
        # window read; bounded by _flush_health to the same depth as
        # the loss window
        self._health_pending: "deque" = deque()
        self._layer_stats_fn = None
        self._layer_group_names = None
        # per-step phase timelines (observability.trace): h2d / dispatch
        # phases plus input-wait / loss-sync / checkpoint-stall waits
        # handed over from the prefetcher, step() window and
        # CheckpointManager; derives mxnet_step_overlap_fraction — the
        # host-blocking view of how much of the dispatch+collective
        # window (incl. the ZeRO param all-gather) overlapped compute
        self._timeline = _trace.StepTimeline("train_step")
        self._timeline_multi = _trace.StepTimeline("train_step_multi")
        self._jitted = self._build(donate)

    # ------------------------------------------------------- zero layout
    def _init_state(self, i: int, p):
        """Optimizer state for param slot ``i``. In zero mode, diff-slot
        state is created over the FLAT PADDED weight (shape ``(n_pad,)``)
        so every weight-shaped moment buffer can shard 1/dp per replica;
        with compression on, the per-shard error-feedback residual rides
        in the state pytree as ``(state, residual)`` — it must persist,
        checkpoint and donate exactly like a moment buffer."""
        w = p.data()
        if not self.zero or i not in set(self.model.diff_slots):
            return self.optimizer.create_state(i, w)
        n = int(onp.prod(w.shape) or 1)
        bits, block = self._compression or (8, None)
        n_pad, chunk, block_eff = _quant.zero_layout(
            n, self._dp, block, bits)
        self._zero_meta[i] = (n, n_pad, chunk, block_eff)
        flat = jnp.pad(w._data.reshape(-1), (0, n_pad - n))
        st = self.optimizer.create_state(i, NDArray(flat))
        if self._compression is not None:
            st = (st, jnp.zeros((n_pad,), jnp.float32))
        return st

    def _zero_state_sharding(self, slot: int):
        """Per-leaf placement of a zero-mode state pytree: weight-shaped
        ``(n_pad,)`` leaves shard over 'dp', everything else (scalar
        clocks/seeds) replicates."""
        n_pad = self._zero_meta[slot][1]
        sharded = NamedSharding(self.mesh, P("dp"))
        repl = NamedSharding(self.mesh, P())

        def place(x):
            if getattr(x, "ndim", None) == 1 and x.shape[0] == n_pad:
                return jax.device_put(x, sharded)
            return jax.device_put(x, repl)

        return place

    def zero_state_bytes(self):
        """``(per_replica, replicated_equiv)`` optimizer-state bytes,
        computed from the LIVE shardings (no device sync): per_replica
        sums each leaf's shard shape on one device, replicated_equiv is
        the unsharded footprint a plain data-parallel replica holds.
        Also refreshes the ``mxnet_zero_*`` gauges."""
        per_replica = 0
        total = 0
        for st in self._opt_states:
            for leaf in jax.tree.leaves(st):
                if not hasattr(leaf, "shape"):
                    continue
                nbytes = int(onp.prod(leaf.shape) or 1) * leaf.dtype.itemsize
                total += nbytes
                sh = getattr(leaf, "sharding", None)
                if sh is not None:
                    shard = sh.shard_shape(tuple(leaf.shape))
                    per_replica += int(onp.prod(shard) or 1) * \
                        leaf.dtype.itemsize
                else:
                    per_replica += nbytes
        if _metrics.ENABLED:
            _metrics.ZERO_SHARDS.set(self._dp if self.zero else 0)
            _metrics.ZERO_STATE_BYTES.labels(scope="per_replica").set(
                per_replica)
            _metrics.ZERO_STATE_BYTES.labels(
                scope="replicated_equiv").set(total)
        return per_replica, total

    def zero_residual_norms(self):
        """slot -> L2 of the quantization error-feedback residual (device
        reduction + one host read per slot — on-demand observability, not
        a per-step cost). Updates ``mxnet_zero_residual_l2``."""
        out = {}
        if self._compression is None:
            return out
        for slot in self.model.diff_slots:
            st = self._opt_states[slot]
            if not (isinstance(st, tuple) and len(st) == 2
                    and slot in self._zero_meta):
                continue
            norm = float(jnp.linalg.norm(st[1]))
            out[slot] = norm
            if _metrics.ENABLED:
                _metrics.ZERO_RESIDUAL.labels(slot=str(slot)).set(norm)
        return out

    # ------------------------------------------------------------------
    def _build(self, donate: bool):
        model = self.model
        opt = self.optimizer
        loss_fn = self.loss_fn
        diff_slots = list(model.diff_slots)
        lr_mults = [p.lr_mult for p in model.params]
        wd_mults = [p.wd_mult for p in model.params]

        use_remat = self.remat
        zero = self.zero
        zmeta = self._zero_meta
        comp = self._compression
        health_on = self._health_on
        skip_on = health_on and self.health.config.on_anomaly == "skip"
        mesh = self.mesh
        param_specs = [p.sharding if getattr(p, "sharding", None) is not None
                       else P() for p in model.params]

        def _cst(x, spec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))

        def _count_zero(op, nbytes):
            # runs at TRACE time (same contract as collectives._count):
            # one tick = bytes one execution of this program moves
            if _metrics.ENABLED:
                _metrics.record_io(_metrics.COLLECTIVE_CALLS,
                                   _metrics.COLLECTIVE_BYTES, nbytes, op=op)

        def zero_update(slot, w, g, state, lr_s, wd_s, t, rescale):
            """ZeRO update of one param: reduce grads onto this replica's
            flat shard, step the shard-resident optimizer state, then
            all-gather fresh params (optionally as quantized deltas)."""
            n, n_pad, chunk, block_eff = zmeta[slot]
            res = None
            if comp is not None:
                state, res = state
            gf = (g * rescale).reshape(-1)
            if n_pad > n:
                gf = jnp.pad(gf, (0, n_pad - n))
            if zero == 1:
                # ZeRO-1 wire: full all-reduce first, THEN slice the shard
                # (grads replicate; only optimizer state shards)
                gf = _cst(gf, P())
                _count_zero("zero_allreduce", n_pad * gf.dtype.itemsize)
            else:
                _count_zero("zero_reduce_scatter", n_pad * gf.dtype.itemsize)
            g_sh = _cst(gf, P("dp"))
            wf = w.reshape(-1)
            if n_pad > n:
                wf = jnp.pad(wf, (0, n_pad - n))
            w_sh = _cst(wf, P("dp"))
            nw_sh, ns = opt.update_step(w_sh, g_sh, state, lr_s, wd_s, t)
            ns = jax.tree.map(lambda o, nv: nv.astype(o.dtype), state, ns)
            if comp is None:
                nw_full = _cst(nw_sh.astype(w.dtype), P())  # all-gather
                _count_zero("zero_allgather", n_pad * w.dtype.itemsize)
            else:
                bits, _ = comp
                # quantize the param DELTA per shard; error feedback keeps
                # the dropped bits in the shard for the next step
                delta = (nw_sh.astype(jnp.float32)
                         - w_sh.astype(jnp.float32)) + res
                codes, scales = _quant.quantize_blocks(delta, bits, block_eff)
                new_res = _cst(
                    delta - _quant.dequantize_blocks(codes, scales,
                                                     block_eff), P("dp"))
                packed = _quant.pack_codes(codes, bits)
                # only codes + scales cross the dp axis
                packed_f = _cst(packed, P())
                scales_f = _cst(scales, P())
                _count_zero("zero_allgather_q",
                            _quant.wire_bytes(n_pad, bits, block_eff))
                delta_f = _quant.dequantize_blocks(
                    _quant.unpack_codes(packed_f, bits), scales_f, block_eff)
                nw_full = (wf.astype(jnp.float32) + delta_f).astype(w.dtype)
                ns = (ns, new_res)
            nw = nw_full[:n].reshape(w.shape)
            return _cst(nw, param_specs[slot]), ns

        def step_fn(param_vals, opt_states, batch, lr, t, seed, rescale):
            inputs, labels = batch

            def apply_model(full, ins):
                return model.apply(full, *ins, seed=seed, training=True)

            if use_remat:
                apply_model = jax.checkpoint(apply_model)

            def loss_of(diff_vals):
                full = list(param_vals)
                for slot, v in zip(diff_slots, diff_vals):
                    full[slot] = v
                outs, aux = apply_model(full, inputs)
                with jax.named_scope("mx.loss"):
                    if labels is None:
                        loss = loss_fn(outs)
                    else:
                        loss = loss_fn(outs, *labels)
                    if isinstance(loss, NDArray):
                        loss = loss._data
                    return jnp.mean(loss), aux

            diff_vals = [param_vals[i] for i in diff_slots]
            # forward and backward are traced inside this call: kernels
            # GSPMD cannot partition find the mesh to map themselves over
            with _mesh_mod.use_mesh(mesh):
                (loss, aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(diff_vals)

            new_params = list(param_vals)
            new_states = list(opt_states)
            with jax.named_scope("mx.optimizer"):
                for slot, g in zip(diff_slots, grads):
                    w = param_vals[slot]
                    lr_s = lr * lr_mults[slot]
                    wd_s = jnp.float32(opt.wd * wd_mults[slot])
                    if zero:
                        new_params[slot], new_states[slot] = zero_update(
                            slot, w, g, opt_states[slot], lr_s, wd_s, t,
                            rescale)
                        continue
                    nw, ns = opt.update_step(
                        w, g * rescale, opt_states[slot], lr_s, wd_s, t)
                    # fp32 scalar hyperparams promote bf16 weights/state;
                    # keep the stored dtype stable (also a fori_loop carry
                    # invariant)
                    new_params[slot] = nw.astype(w.dtype)
                    new_states[slot] = jax.tree.map(
                        lambda o, n: n.astype(o.dtype), opt_states[slot], ns)
            for slot, v in aux.items():
                new_params[slot] = v
            if not health_on:
                return tuple(new_params), tuple(new_states), loss
            # mxhealth: fixed-shape reductions fused into THIS program —
            # returned beside the loss and read on the lazy window's
            # deferred schedule (no extra executable, no new sync)
            scaled = [g * rescale for g in grads]
            skipped = None
            if skip_on:
                # on_anomaly="skip": drop the whole state transition
                # bitwise when anything went nonfinite (params select
                # their OLD values — the AMP scaler's skip semantics,
                # including aux running stats, which a poisoned forward
                # also corrupted)
                bad = _health.device_nonfinite_flag(param_vals, scaled,
                                                    loss)
                new_params = [jnp.where(bad, o, n)
                              for o, n in zip(param_vals, new_params)]
                new_states = [jax.tree.map(
                    lambda o, n: jnp.where(bad, o, n), os, ns)
                    for os, ns in zip(opt_states, new_states)]
                skipped = bad
            vec = _health.device_health_vector(
                param_vals, new_params, scaled, loss=loss, skipped=skipped)
            return tuple(new_params), tuple(new_states), loss, vec

        # the device trace's program line reads jit_train_step(...). JAX's
        # persistent cache keys an executable by its program less the
        # metadata, so one renamed scope alone would load the old names
        # back from a warm cache: the name carries the change
        step_fn.__name__ = "train_step"
        self._step_fn = step_fn
        kwargs = {}
        if donate:
            kwargs["donate_argnums"] = (0, 1)
        if self.mesh is not None:
            # Place parameters/optimizer state on their annotated shardings
            # once; GSPMD propagates from committed inputs, and donation pins
            # output shardings to match. Batch arrays are placed per call.
            param_sh = model.shardings(self.mesh)
            placed = [jax.device_put(v, s)
                      for v, s in zip(model.values(), param_sh)]
            model.write_back(placed)
            self._opt_states = [
                jax.tree.map(self._zero_state_sharding(i)
                             if i in self._zero_meta
                             else (lambda x, s=s: jax.device_put(x, s)), st)
                for i, (st, s) in enumerate(zip(self._opt_states, param_sh))]
            if self.zero:
                self.zero_state_bytes()   # publish the mxnet_zero_* gauges
        return jax.jit(step_fn, **kwargs)

    # ------------------------------------------------------------------
    def input_shardings(self):
        """``(data_sharding, label_sharding)`` this step places batches
        with — hand them to ``DataLoader.as_device_iterator`` /
        ``DevicePrefetcher`` so batches arrive pre-placed and the step
        skips its own ``device_put``. ``(None, None)`` without a mesh
        (default-device placement)."""
        if self.mesh is None:
            return (None, None)
        return (NamedSharding(self.mesh, self.data_spec or P()),
                NamedSharding(self.mesh, self.label_spec or P()))

    def _place(self, arrays, spec):
        """device_put a batch tuple onto the mesh, skipping arrays the
        prefetcher already placed there (re-putting a committed array is
        a dispatch + potential copy on the critical path). One contract,
        one implementation: pipeline.stage_batch is what the prefetcher
        runs, so handoff and fallback can never disagree."""
        from ..pipeline import stage_batch
        return tuple(stage_batch(
            tuple(arrays), NamedSharding(self.mesh, spec or P())))

    # ------------------------------------------------------------------
    def __call__(self, inputs, labels=None):
        """Run one step; updates net parameters/optimizer state in place;
        returns the scalar loss as NDArray."""
        with _profiler.scope("mx.train.step", "train") as span:
            out = self._call_impl(inputs, labels)
        if _metrics.ENABLED:
            self._observe_step(inputs, span.seconds, 1, "train_step")
        return out

    def step(self, inputs, labels=None):
        """Windowed dispatch: identical computation to ``__call__`` but
        the returned loss is a LAZY handle — nothing forces device
        execution here, so dispatch runs ahead of the device instead of
        re-synchronizing once per step (the ``float(loss)``-every-step
        anti-pattern). With ``block_every=W`` set, at most W losses stay
        unforced in flight; the call blocks on the loss from W steps ago
        once the window fills. Bitwise-identical to the synchronous loop
        (same executables, same order) — only the host sync points move.
        Call :meth:`drain` after the loop (or force any returned loss)
        to retire the window."""
        out = self(inputs, labels)
        self._inflight.append(out._data)
        w = self.block_every
        if w and len(self._inflight) > w:
            # only time ACTUAL blocking: a zero-duration sample per
            # non-blocking step would flood the loss_sync histogram and
            # collapse its percentiles toward zero
            with _profiler.scope("mx.train.loss_sync", "train") as sync:
                while len(self._inflight) > w:
                    jax.block_until_ready(self._inflight.popleft())
            if _metrics.ENABLED or _trace.ENABLED:
                # host blocked on the loss from W steps ago: charge it to
                # the NEXT step's timeline as the loss_sync phase
                _trace.note_blocked("loss_sync", sync.seconds)
        if _metrics.ENABLED:
            _metrics.PIPELINE_DEPTH.labels(path="train_step").set(
                len(self._inflight))
        return out

    def drain(self):
        """Block until every loss dispatched through :meth:`step` has
        actually executed (the end-of-epoch / pre-checkpoint barrier)."""
        if self._inflight:
            with _profiler.scope("mx.train.loss_sync", "train") as sync:
                while self._inflight:
                    jax.block_until_ready(self._inflight.popleft())
            if _metrics.ENABLED or _trace.ENABLED:
                _trace.note_blocked("loss_sync", sync.seconds)
        if self._health_on:
            self._flush_health(0)
        if _metrics.ENABLED:
            _metrics.PIPELINE_DEPTH.labels(path="train_step").set(0)

    # --------------------------------------------------------- mxhealth
    def _queue_health(self, step_no: int, hvec):
        """Park one device health vector for deferred reading. The
        handle is NOT forced here — like the lazy loss it stays in
        flight until the window pushes it out."""
        self._health_pending.append((step_no, hvec))

    def _flush_health(self, limit: Optional[int] = None):
        """Deliver pending health vectors to the monitor, keeping at
        most ``limit`` in flight (default: the loss window depth, so
        health reads ride the exact same deferred schedule as the
        loss — a vector is only forced once it is W steps old and its
        step already executed; no NEW sync points appear)."""
        if limit is None:
            limit = self.block_every or 8
        while len(self._health_pending) > limit:
            step_no, hvec = self._health_pending.popleft()
            self.health.observe(step_no, onp.asarray(hvec))

    def read_health(self):
        """Force every pending health vector through the monitor and
        return the most recent one as a name→value dict (None before
        the first step). An explicit sync point — tests and drills use
        it; the training loop never needs to."""
        if not self._health_on:
            raise MXNetError("read_health(): TrainStep built with "
                             "health=False")
        self._flush_health(0)
        return self.health.last_vector()

    def health_verdict(self):
        """Flush pending vectors, then the monitor's verdict — the
        ``CheckpointManager(health=...)`` provider, so a save can never
        be tagged healthy on the strength of vectors still in flight
        (None when health is off). A halt-policy trigger during the
        flush is swallowed here: it is already recorded, and the
        verdict below reports the taint — tagging must not kill the
        save."""
        if not self._health_on:
            return None
        try:
            self._flush_health(0)
        except _health.NumericAnomalyError:
            pass
        return self.health.verdict()

    def _maybe_sample_layers(self):
        every = (self.health.config.sample_every if self._health_on else 0)
        if every and self._step % every == 0:
            self.sample_layer_stats()

    def sample_layer_stats(self):
        """Per-layer-group max-abs / RMS of the current params via ONE
        cached jitted reduction (built on first use, steady-state
        recompile-free; deliberately NOT counted in
        ``mxnet_recompilations_total`` — it is not the step program).
        The host read here is the subsystem's only non-deferred sync,
        on the coarse ``sample_every`` cadence. Returns
        group → {"maxabs": .., "rms": ..} and refreshes the
        ``mxnet_health_layer_*`` gauges."""
        if self._layer_stats_fn is None:
            groups = {}
            for i, (name, p) in enumerate(self.model.param_items):
                if not jnp.issubdtype(p.data()._data.dtype, jnp.floating):
                    continue
                groups.setdefault(
                    _health.layer_group_of(name), []).append(i)
            names = sorted(groups)
            idx_of = [groups[g] for g in names]

            def stats(param_vals):
                out = []
                for idxs in idx_of:
                    flat = jnp.concatenate(
                        [param_vals[i].astype(jnp.float32).reshape(-1)
                         for i in idxs])
                    out.append(jnp.stack([
                        jnp.max(jnp.abs(flat)),
                        jnp.sqrt(jnp.mean(flat * flat))]))
                return jnp.stack(out) if out else jnp.zeros((0, 2))

            self._layer_group_names = names
            self._layer_stats_fn = jax.jit(stats)
        vals = onp.asarray(self._layer_stats_fn(
            tuple(self.model.values())))
        out = {}
        for g, (maxabs, rms) in zip(self._layer_group_names, vals):
            out[g] = {"maxabs": float(maxabs), "rms": float(rms)}
            if _metrics.ENABLED:
                _metrics.HEALTH_LAYER_MAXABS.labels(group=g).set(
                    float(maxabs))
                _metrics.HEALTH_LAYER_RMS.labels(group=g).set(float(rms))
        return out

    @staticmethod
    def _observe_step(inputs, dt: float, steps: int, path: str):
        """Step-time histogram + examples throughput (host wall time; PJRT
        dispatch is async so un-synced steps read as dispatch latency)."""
        _metrics.STEP_TIME.labels(path=path).observe(dt)
        x0 = inputs[0] if isinstance(inputs, (tuple, list)) else inputs
        shape = getattr(x0, "shape", ())
        examples = (shape[0] if shape else 1) * steps
        _metrics.EXAMPLES.labels(path=path).inc(examples)
        if dt > 0:
            _metrics.EXAMPLES_PER_SEC.labels(path=path).set(examples / dt)
            # live roofline: most recent dispatch wall time against the
            # cost ledger's executable entry for this path. work=steps:
            # XLA cost analysis counts a fori_loop body ONCE, so the
            # multi-step entry holds one iteration's cost and the note
            # scales it to the whole dispatched window (bench.py's
            # work_per_run convention)
            _perf.note_step(path, dt, work=steps)

    def _track_retrace(self, batch_sig, steps=None):
        """Count (and warn-log) jit retraces of the fused step. jax.jit
        caches EVERY signature it has seen, so only a genuinely new
        (batch signature, executable) pair is a recompilation —
        alternating between two known shapes compiles nothing and must
        not count (or warn). ``steps`` keys the executable: __call__ runs
        the single-step program (None), run() compiles one multi-step
        program per ``steps`` value, and each is its own compile event."""
        key = (batch_sig, steps)
        if key in self._seen_batch_sigs:
            return
        retrace = bool(self._seen_batch_sigs)
        self._seen_batch_sigs.add(key)
        if retrace:
            logger.warning(
                "TrainStep: recompilation #%d — new batch signature %s"
                "%s", len(self._seen_batch_sigs) - 1, batch_sig,
                "" if steps is None else f" (multi-step, steps={steps})")
        if _metrics.ENABLED:
            _metrics.RECOMPILATIONS.labels(
                block="TrainStep",
                kind="retrace" if retrace else "initial").inc()

    def _aot_exec(self, batch_sig, steps, jitted, args):
        """Executable for one (batch signature, steps) pair. With the
        persistent AOT cache enabled, a warm restart deserializes the
        fused-step executable from disk instead of recompiling it (the
        preemption-resume path: CheckpointManager restores the params,
        this restores the program). Donation and the multi-step count are
        folded into the fingerprint — they don't show in the module
        text."""
        key = (batch_sig, steps)
        fn = self._aot_execs.get(key)
        if fn is None:
            label = "train_step" if steps is None else "train_step_multi"
            from .. import aot as _aot
            if _aot.get_cache() is not None:
                fn = _aot.compile_cached(
                    jitted, args, label=label,
                    extra={"donate": self._donate, "steps": steps})
            else:
                fn = jitted
                # cost-ledger capture, once per (signature, steps)
                # executable (compile_cached records the same entry on
                # the AOT path). XLA cost analysis counts the fori_loop
                # body ONCE, so the multi-step entry carries one
                # iteration's cost; _observe_step's note scales it by
                # the dispatched step count
                _perf.capture_build(
                    label, jitted, args,
                    meta={"steps": steps, "zero": self.zero,
                          "donate": self._donate})
            self._aot_execs[key] = fn
        return fn

    def _call_impl(self, inputs, labels=None):
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        if labels is not None and not isinstance(labels, (tuple, list)):
            labels = (labels,)
        tl = self._timeline.begin()
        try:
            return self._call_body(tl, inputs, labels)
        finally:
            # finish in finally: a raise mid-step (shape error, failed
            # collective) must not leave the timeline active with a
            # stale overlap window poisoning the next step's gauge
            self._timeline.finish()

    def _call_body(self, tl, inputs, labels):
        in_data = tuple(x._data if isinstance(x, NDArray) else jnp.asarray(x)
                        for x in inputs)
        lb_data = None if labels is None else tuple(
            x._data if isinstance(x, NDArray) else jnp.asarray(x) for x in labels)
        if self.mesh is not None:
            with tl.phase("h2d"):
                in_data = self._place(in_data, self.data_spec)
                if lb_data is not None:
                    lb_data = self._place(lb_data, self.label_spec)
        self._step += 1
        self.optimizer.num_update = self._step
        lr = jnp.float32(self.optimizer.learning_rate)
        t = jnp.int32(self._step)
        # deterministic per-step dropout stream; derived host-side (no eager
        # RNG op per step — that would cost a device round trip)
        seed = t
        args = (tuple(self.model.values()), tuple(self._opt_states),
                (in_data, lb_data), lr, t, seed,
                jnp.float32(self.optimizer.rescale_grad))
        batch_sig = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                                 (in_data, lb_data))
        self._track_retrace(batch_sig)
        if self._last_avals is None or batch_sig != self._last_batch_sig:
            # keep shardings so cost_analysis lowers the same partitioned
            # program the step actually runs; refresh when the batch
            # signature changes (jit retraces then too)
            self._last_batch_sig = batch_sig
            self._last_avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=getattr(x, "sharding", None)), args)
        with tl.phase("dispatch"), \
                _elastic.armed_watchdog("train_step.dispatch"):
            # the armed window bounds the dispatch's wall time: a dead dp
            # peer shows up here as a grad/param collective that never
            # completes, and the elastic watchdog turns that hang into a
            # detection event instead of a silent stuck job
            out = self._aot_exec(
                batch_sig, None, self._jitted, args)(*args)
        if self._health_on:
            params, states, loss, hvec = out
            self._queue_health(self._step, hvec)
        else:
            params, states, loss = out
        self.model.write_back(params)
        self._opt_states = list(states)
        if self._health_on:
            self._flush_health()
            self._maybe_sample_layers()
        return NDArray(loss)

    def _get_multi(self, steps: int):
        fn = self._multi_cache.get(steps)
        if fn is None:
            step_fn = self._step_fn
            health_on = self._health_on
            # sticky indices accumulate with max across the window: a
            # transient mid-window NaN or skip must survive to the one
            # vector the window returns; norms/loss keep the last step
            sticky = onp.zeros((_health.VEC_LEN,), bool)
            sticky[list(_health.STICKY_IDX)] = True

            def multi(param_vals, opt_states, batch, lrs, t0, rescale):
                def body(i, carry):
                    if health_on:
                        params, states, _, hacc = carry
                    else:
                        params, states, _ = carry
                    t = t0 + i
                    out = step_fn(params, states, batch, lrs[i], t, t,
                                  rescale)
                    if health_on:
                        p, s, loss, hv = out
                        hacc = jnp.where(jnp.asarray(sticky),
                                         jnp.maximum(hacc, hv), hv)
                        return (p, s, loss.astype(jnp.float32), hacc)
                    p, s, loss = out
                    return (p, s, loss.astype(jnp.float32))

                init = (tuple(param_vals), tuple(opt_states), jnp.float32(0))
                if health_on:
                    init = init + (jnp.zeros((_health.VEC_LEN,),
                                             jnp.float32),)
                return jax.lax.fori_loop(0, steps, body, init)

            kwargs = {"donate_argnums": (0, 1)} if self._donate else {}
            fn = jax.jit(multi, **kwargs)
            self._multi_cache[steps] = fn
        return fn

    def run(self, inputs, labels=None, steps: int = 1):
        """Run ``steps`` updates on the same batch inside ONE executable
        (lax.fori_loop over the fused step). Each dispatch through PJRT
        costs host time; looping on device amortizes that and keeps donated params/state resident in
        HBM across iterations. The per-iteration step counter still
        advances, so momentum/Adam bias correction match ``steps`` separate
        calls. Returns the last step's loss."""
        if steps == 1:
            return self(inputs, labels)
        t_start = time.perf_counter() if _metrics.ENABLED else None
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        if labels is not None and not isinstance(labels, (tuple, list)):
            labels = (labels,)
        tl = self._timeline_multi.begin()
        try:
            return self._run_body(tl, inputs, labels, steps, t_start)
        finally:
            self._timeline_multi.finish()

    def _run_body(self, tl, inputs, labels, steps, t_start):
        in_data = tuple(x._data if isinstance(x, NDArray) else jnp.asarray(x)
                        for x in inputs)
        lb_data = None if labels is None else tuple(
            x._data if isinstance(x, NDArray) else jnp.asarray(x)
            for x in labels)
        if self.mesh is not None:
            with tl.phase("h2d"):
                in_data = self._place(in_data, self.data_spec)
                if lb_data is not None:
                    lb_data = self._place(lb_data, self.label_spec)
        t0 = jnp.int32(self._step + 1)
        # per-iteration lr so an lr_scheduler sees every step, exactly as
        # N separate calls would (scheduler runs host-side; the schedule
        # for this window ships as an array)
        lrs = []
        for i in range(steps):
            self.optimizer.num_update = self._step + 1 + i
            lrs.append(self.optimizer.learning_rate)
        lrs = jnp.asarray(lrs, jnp.float32)
        self._step += steps
        self.optimizer.num_update = self._step
        rescale = jnp.float32(self.optimizer.rescale_grad)
        batch_sig = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                                 (in_data, lb_data))
        self._track_retrace(batch_sig, steps)
        if self._last_avals is None or batch_sig != self._last_batch_sig:
            # cost_analysis() reports the SINGLE-step program
            args = (tuple(self.model.values()), tuple(self._opt_states),
                    (in_data, lb_data), lrs[0], t0, t0, rescale)
            self._last_batch_sig = batch_sig
            self._last_avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=getattr(x, "sharding", None)), args)
        multi_args = (tuple(self.model.values()), tuple(self._opt_states),
                      (in_data, lb_data), lrs, t0, rescale)
        with tl.phase("dispatch"), \
                _elastic.armed_watchdog("train_step_multi.dispatch"):
            out = self._aot_exec(
                batch_sig, steps, self._get_multi(steps),
                multi_args)(*multi_args)
        if self._health_on:
            params, states, loss, hvec = out
            self._queue_health(self._step, hvec)
        else:
            params, states, loss = out
        self.model.write_back(params)
        self._opt_states = list(states)
        if self._health_on:
            self._flush_health()
            self._maybe_sample_layers()
        if t_start is not None:
            self._observe_step(in_data, time.perf_counter() - t_start,
                               steps, "train_step_multi")
        return NDArray(loss)

    def state_arrays(self):
        """Flat name→array view of the optimizer state (plus the step
        clock), for sharded checkpointing (checkpoint.CheckpointManager
        sharded mode). Arrays keep their live shardings."""
        import jax.tree_util as jtu
        out = {}
        for slot, st in enumerate(self._opt_states):
            leaves = jtu.tree_leaves(st)
            for i, leaf in enumerate(leaves):
                out[f"opt{slot}.{i}"] = leaf
        return out

    def write_state_arrays(self, arrays):
        """Inverse of ``state_arrays``: writes loaded values back into the
        optimizer state pytrees (same structure required)."""
        import jax.tree_util as jtu
        new_states = []
        for slot, st in enumerate(self._opt_states):
            leaves, treedef = jtu.tree_flatten(st)
            new_leaves = [arrays[f"opt{slot}.{i}"] for i in range(len(leaves))]
            new_states.append(jtu.tree_unflatten(treedef, new_leaves))
        self._opt_states = new_states

    def compiled(self):
        """Compiled XLA executable of the current single-step signature
        (after at least one step) — the PUBLIC accessor for cost/memory
        analysis and optimized-HLO inspection
        (``observability.hlo.analyze_compiled``), replacing the
        ``step._jitted.lower(*step._last_avals)`` reach-in the benchmark
        scripts used. The in-memory AOT compile cache makes repeated
        calls cheap."""
        if self._last_avals is None:
            raise MXNetError(
                "TrainStep.compiled(): no signature yet; run at least "
                "one step first")
        return self._jitted.lower(*self._last_avals).compile()

    def cost_analysis(self):
        """XLA cost analysis of the step ({'flops': ...}, etc.); call after
        at least one step. Used for MFU reporting in bench.py. Prefers the
        lowered-stage analysis (no second compile)."""
        if self._last_avals is None:
            return None
        lowered = self._jitted.lower(*self._last_avals)
        try:
            ca = lowered.cost_analysis()
        except Exception:
            ca = None
        if not ca:  # some backends only do cost analysis post-compile;
            ca = lowered.compile().cost_analysis()  # cache makes this cheap
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        return ca
