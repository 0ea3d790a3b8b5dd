"""Quantized multi-token decode: the int8/int4 weight GEMVs, the on-device
multi-token decode loop, fused LM-head sampling, vocab padding, and launch
accounting (a kernel's kind where the kernel runs, ``reference`` where the
XLA reference runs in its place)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np
from mxnet_tpu.contrib.quantization import quantize_net
from mxnet_tpu.models import GPTModel, generate
from mxnet_tpu.models import generation as gen
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.ops import fused_block_gemv as fb
from mxnet_tpu.ops.int8_gemv import count_launches


def _gpt(vocab=251, hidden=48, layers=2, heads=4, maxpos=64, seed=0):
    """Odd-shaped by default: vocab 251 (prime; pads to 256), hidden 48
    (not a 128 multiple) — exercises the non-multiple D/V fallback
    routing the parity contract covers."""
    mx.random.seed(seed)
    net = GPTModel(GPTConfig(vocab_size=vocab, hidden_size=hidden,
                             num_layers=layers, num_heads=heads,
                             max_position_embeddings=maxpos, dropout=0.0))
    net.initialize()
    net(np.array(onp.zeros((1, 4), "int32")))   # concretize param shapes
    return net


def _quantized(vocab=251, hidden=48, bits=8, **kw):
    net = _gpt(vocab=vocab, hidden=hidden, **kw)
    quantize_net(net, calib_mode="none", bits=bits)
    return net


@pytest.fixture(scope="module")
def net256():
    """The lane-aligned int8 net the kernel parity tests share
    (read-only)."""
    return _quantized(vocab=256, hidden=256, heads=4)


@pytest.fixture(scope="module")
def net256_int4():
    """Same shape, bits=4 packed-nibble weights."""
    return _quantized(vocab=256, hidden=256, heads=4, bits=4)


# ---------------------------------------------------------------- fused GEMV
def test_vocab_padding_and_sliced_logits():
    """The int8 tied head is padded to a 128-lane multiple; logits are
    sliced back to V and match the unpadded dequantized matmul."""
    net = _quantized(vocab=251, hidden=48)
    w_q, scale, V = net._q_lm_head
    assert V == 251 and w_q.shape[0] == fb.pad_vocab(251) == 256
    assert w_q.shape[0] % fb.VOCAB_LANE == 0
    # pad rows are exact zeros (scale 1) so they cannot win any argmax
    assert (onp.asarray(w_q[V:]) == 0).all()
    assert (onp.asarray(scale[V:]) == 1.0).all()
    rng = onp.random.RandomState(0)
    p = np.array(rng.randint(0, 251, (2, 6)).astype("int32"))
    logits = net(p).asnumpy()                 # 12 rows -> int8 head path
    assert logits.shape[-1] == V


def test_fused_head_sample_matches_host_sample_tokens():
    """fused_lm_head_sample's XLA path must equal materialized-logits +
    sample_tokens bitwise (same fold_in keys) for greedy AND filtered
    sampling rows."""
    import jax
    import jax.numpy as jnp
    net = _quantized(vocab=251, hidden=48)
    w_q, scale, V = net._q_lm_head
    rng = onp.random.RandomState(2)
    B = 6
    h = jnp.asarray(rng.randn(B, 48), jnp.float32)
    temps = jnp.asarray([0.0, 1.0, 0.7, 0.0, 1.3, 0.5], jnp.float32)
    topks = jnp.asarray([0, 5, 0, 3, 8, 0], jnp.int32)
    topps = jnp.asarray([1.0, 0.9, 0.8, 1.0, 1.0, 0.95], jnp.float32)
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.key(s), 7))(
        jnp.arange(B, dtype=jnp.uint32))
    got = fb.fused_lm_head_sample(h, w_q, scale, V, keys, temps, topks,
                                  topps)
    logits = (h @ (w_q.astype(jnp.float32) * scale[:, None]).T)[:, :V]
    want = gen.sample_tokens(logits, keys, temps, topks, topps)
    assert (onp.asarray(got) == onp.asarray(want)).all()


def test_head_kernel_interpret_parity(net256):
    """The REAL fused-head kernel, run in Pallas interpret mode on CPU:
    greedy rows are exactly argmax, sampled rows are in-vocab and
    deterministic per key."""
    import jax.numpy as jnp
    net = net256
    rng = onp.random.RandomState(0)
    B, D = 3, 256
    w_q, scale, V = net._q_lm_head
    h = jnp.asarray(rng.randn(B, D), jnp.float32)
    kb = jnp.asarray(rng.randint(0, 2 ** 31, B), jnp.uint32)
    tok = fb._head_kernel(h, w_q, scale, V, jnp.zeros((B,), jnp.float32),
                          kb, interpret=True)
    logits = fb._deq_matmul(h, w_q, scale)[:, :V]
    assert (onp.asarray(tok) == onp.asarray(jnp.argmax(logits, -1))).all()
    # sampled rows: in-vocab + deterministic per key
    t1 = fb._head_kernel(h, w_q, scale, V, jnp.full((B,), 0.8, jnp.float32),
                         kb, interpret=True)
    t2 = fb._head_kernel(h, w_q, scale, V, jnp.full((B,), 0.8, jnp.float32),
                         kb, interpret=True)
    assert (onp.asarray(t1) == onp.asarray(t2)).all()
    assert (onp.asarray(t1) < V).all()


def test_device_sampling_matches_host_sample_tokens():
    """decode_multi_tokens' device-side sampling must emit EXACTLY the
    tokens a host loop of decode_step + sample_tokens emits with the same
    fold_in streams (the statistical-parity contract is exact off-TPU)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.functional import functionalize
    from mxnet_tpu.ndarray import NDArray
    net = _gpt(vocab=64, hidden=32, heads=2)
    B, P, K = 3, 4, 5
    rng = onp.random.RandomState(3)
    prompt = rng.randint(1, 60, (B, P)).astype(onp.int32)
    fm = functionalize(net, NDArray(prompt), training=False)
    values = tuple(fm.values())
    L = 32
    temps = jnp.asarray([0.0, 1.0, 0.6], jnp.float32)
    topks = jnp.asarray([0, 6, 0], jnp.int32)
    topps = jnp.asarray([1.0, 0.9, 1.0], jnp.float32)
    seeds = jnp.asarray([11, 22, 33], jnp.uint32)

    def prefill():
        caches = tuple(jnp.zeros(s, d) for s, d in net.cache_spec(B, L))
        logits, caches = gen.decode_step(fm, values, jnp.asarray(prompt),
                                         jnp.int32(0), caches)
        keys = gen._fold_keys(seeds, jnp.zeros((B,), jnp.int32))
        tok0 = gen.sample_tokens(logits[:, -1], keys, temps, topks, topps)
        return tok0, caches

    # host reference: one step + one host sample at a time
    tok, caches = prefill()
    host = []
    for j in range(K):
        logits, caches = gen.decode_step(fm, values, tok[:, None],
                                         jnp.full((B,), P + j, jnp.int32),
                                         caches)
        keys = gen._fold_keys(seeds, jnp.full((B,), 1 + j, jnp.int32))
        tok = gen.sample_tokens(logits[:, -1], keys, temps, topks, topps)
        host.append(onp.asarray(tok))
    host = onp.stack(host, axis=1)                      # [B, K]

    # device: the whole K-token loop in one dispatch
    tok0, caches = prefill()
    toks, last, steps, _done, _ = gen.decode_multi_tokens(
        fm, values, tok0, jnp.full((B,), P, jnp.int32), caches, K,
        temps, topks, topps, seeds, jnp.ones((B,), jnp.int32))
    assert int(steps) == K
    assert (onp.asarray(toks) == host).all()
    assert (onp.asarray(last) == host[:, -1]).all()


def test_device_sampling_distribution():
    """Sanity: device-side temperature sampling follows the categorical
    distribution (chi-square-ish bound on a 3-way logit gap)."""
    import jax
    import jax.numpy as jnp
    logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.1]], jnp.float32))
    N = 400
    keys = jax.vmap(lambda c: jax.random.fold_in(jax.random.key(9), c))(
        jnp.arange(N, dtype=jnp.int32))
    toks = gen.sample_tokens(jnp.tile(logits, (N, 1)), keys,
                             jnp.ones((N,), jnp.float32),
                             jnp.zeros((N,), jnp.int32),
                             jnp.ones((N,), jnp.float32))
    freq = onp.bincount(onp.asarray(toks), minlength=3) / N
    assert abs(freq[0] - 0.6) < 0.1 and abs(freq[2] - 0.1) < 0.07


@pytest.mark.slow  # heaviest multi-token variant (~17 s): generate()-
# level greedy parity across K; the engine-level multi-token parity +
# EOS/roundtrip tests stay tier-1 per the 870 s budget
def test_generate_multi_token_greedy_parity():
    """generate(multi_token=K) greedy output must be bitwise identical to
    the single-token loop, including EOS fill and K not dividing
    max_new_tokens."""
    net = _quantized()
    rng = onp.random.RandomState(4)
    p = np.array(rng.randint(0, 251, (2, 5)).astype("int32"))
    ref = generate(net, p, 9).asnumpy()
    for K in (2, 3, 4):
        got = generate(net, p, 9, multi_token=K).asnumpy()
        assert (got == ref).all(), K
    eos = int(ref[0, 8])
    ref_eos = generate(net, p, 9, eos_token_id=eos).asnumpy()
    got_eos = generate(net, p, 9, eos_token_id=eos, multi_token=4).asnumpy()
    assert (got_eos == ref_eos).all()


def test_generate_multi_token_validation():
    net = _gpt()
    p = np.array(onp.ones((1, 4), "int32"))
    with pytest.raises(mx.MXNetError, match="multi_token"):
        generate(net, p, 4, multi_token=0)
    with pytest.raises(mx.MXNetError, match="multi_token"):
        generate(net, p, 4, multi_token=2, use_cache=False)


# ------------------------------------------------------------------ launches
@pytest.fixture
def as_tpu(monkeypatch):
    """Make the kernel gates answer as on a TPU, for TRACES only (the
    tally is taken at trace time; nothing is lowered or run)."""
    from mxnet_tpu.ops import int8_gemv
    monkeypatch.setattr(int8_gemv, "on_tpu", lambda: True)
    monkeypatch.setattr(fb, "on_tpu", lambda: True)


def _step_tally(eng, kind="decode", build=None, n=4):
    build = build or eng._build_step
    with count_launches() as tally:
        build(n).trace(*eng._example_args(kind, n))
    return dict(tally)


@pytest.mark.parametrize("bits,gemv", [(8, "gemv"), (4, "gemv_int4")])
def test_decode_launch_accounting_kernel_kinds(as_tpu, bits, gemv):
    """Where the kernels run (gates answering as on a TPU), one engine
    decode step tallies 4 GEMVs/block + 1 head under the GEMV kernel's
    kind, and with multi-token the head moves to the fused-head kernel
    (int8) or its XLA reference (a packed-int4 table has no kernel)."""
    from mxnet_tpu.serve import InferenceEngine
    layers = 3
    net = _quantized(vocab=256, hidden=256, layers=layers, heads=4,
                     bits=bits)
    eng = InferenceEngine(net, max_batch_size=4, max_len=32)
    assert _step_tally(eng) == {gemv: 4 * layers + 1}
    eng2 = InferenceEngine(net, max_batch_size=4, max_len=32, multi_token=2)
    head = "fused_head" if bits == 8 else "reference"
    assert _step_tally(eng2) == {gemv: 4 * layers, head: 1}


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("multi_token", [1, 2])
def test_reference_launch_label_off_tpu(bits, multi_token):
    """Off-TPU every GEMV and head site runs its XLA reference, and the
    tally says so: kind ``reference``, never the kernel's kind."""
    from mxnet_tpu.serve import InferenceEngine
    layers = 2
    net = _quantized(vocab=256, hidden=256, layers=layers, heads=4,
                     bits=bits)
    eng = InferenceEngine(net, max_batch_size=4, max_len=32,
                          multi_token=multi_token)
    assert _step_tally(eng) == {"reference": 4 * layers + 1}


def test_spec_verify_launch_accounting():
    """A speculative verify executable tallies its own spec_verify site
    beside the underlying per-op GEMV sites (the verify forward is
    T-wide, so it keeps the per-matrix dispatch)."""
    from mxnet_tpu.serve import InferenceEngine
    layers = 2
    net = _quantized(vocab=256, hidden=256, layers=layers, heads=4)
    eng = InferenceEngine(net, max_batch_size=2, max_len=32, page_size=8,
                          speculate=3)
    tally = _step_tally(eng, "spec", eng._build_step_spec, n=2)
    assert tally.pop("spec_verify") == 1
    assert tally == {"reference": 4 * layers + 1}


def test_decode_launches_metric_flows():
    from mxnet_tpu import metrics
    was = metrics.enabled()
    metrics.enable()
    try:
        before = metrics.get_sample_value("mxnet_decode_launches_total",
                                          {"kind": "reference"}) or 0
        net = _quantized(vocab=128, hidden=32, layers=1, heads=2)
        p = np.array(onp.ones((1, 4), "int32"))
        generate(net, p, 3).asnumpy()
        after = metrics.get_sample_value("mxnet_decode_launches_total",
                                         {"kind": "reference"})
        assert after and after > before
    finally:
        if not was:
            metrics.disable()


# ------------------------------------------------------- int4 weight-only
def test_int4_gemv_interpret_parity():
    """int4_weight_matmul's REAL kernel in interpret mode: equal to a
    bf16-rounded emulation of its in-VMEM dequant + MXU dot up to f32
    accumulation order, and within bf16 input-rounding distance of the
    f32 codec fallback."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kvstore.quant import (dequantize_blocks, pack_codes,
                                         quantize_blocks, unpack_codes)
    from mxnet_tpu.ops import int8_gemv as ig
    rng = onp.random.RandomState(0)
    M, N, K, block = 3, 384, 256, 128
    w = rng.randn(N, K).astype(onp.float32)
    codes, scales = quantize_blocks(jnp.asarray(w.reshape(-1)), 4, block)
    w_p = pack_codes(codes, 4).reshape(N, K // 2)
    w_s = scales.reshape(N, K // block)
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    ref = ig.int4_weight_matmul(x, w_p, w_s)                 # codec fallback
    ker = ig.int4_weight_matmul(x, w_p, w_s, interpret=True)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(ref - ker))) / scale < 5e-2
    wf = dequantize_blocks(unpack_codes(w_p.reshape(-1), 4),
                           w_s.reshape(-1), block).reshape(N, K)
    emu = jax.lax.dot_general(x.astype(jnp.bfloat16),
                              wf.astype(jnp.bfloat16),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # not bitwise: the kernel contracts the even and the odd nibble plane
    # in two dots and adds them, the emulation in one; the products are
    # the same bf16 values, only the f32 sum associates differently
    onp.testing.assert_allclose(onp.asarray(ker), onp.asarray(emu),
                                rtol=0, atol=1e-5 * scale)


@pytest.mark.parametrize("vocab,hidden", [(251, 48), (256, 256)])
def test_int4_generate_multi_token_parity(vocab, hidden):
    """quantize_net(bits=4): the multi-token loop (fused-head entry, here
    its XLA reference) emits the single-token loop's greedy tokens — at
    a lane-aligned shape and at the odd one."""
    import jax.numpy as jnp
    net = _quantized(vocab=vocab, hidden=hidden, bits=4)
    blk = list(net.blocks)[0]
    assert blk.attn_qkv._w_q.dtype == jnp.uint8
    rng = onp.random.RandomState(1)
    p = np.array(rng.randint(0, vocab, (2, 5)).astype("int32"))
    ref = generate(net, p, 8).asnumpy()
    assert (generate(net, p, 8, multi_token=3).asnumpy() == ref).all()
