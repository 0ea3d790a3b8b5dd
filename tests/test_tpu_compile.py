"""Ask the chip's compiler, without the chip: every ``pallas_call`` site left
in ``mxnet_tpu/ops`` and the whole jitted ``TrainStep`` body compile for a
DESCRIBED v5e at GPT-2-small shapes. Interpret mode cannot see what Mosaic
refuses (unaligned slices, scoped-VMEM overflow, 64-bit index maps); this
file can, at no chip time.

Nothing here touches the TPU library while a module is imported: the topology
is described inside a fixture, by the one xdist worker that is given this
file. The kernels' own TPU gates ask ``device.on_tpu()``, which still sees the
CPU here, so each test steers that predicate itself.
"""
import dataclasses
import importlib
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# GPT-2-small serving/training geometry (models/gpt.py GPT2_SMALL)
D, H, HD, VOCAB, VP = 768, 12, 64, 50257, 50304
B_SERVE, B_TRAIN, T_TRAIN = 8, 16, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, _no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def _no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """The kernel gates answer as on a TPU (they ask ``on_tpu()``, which
    sees this process's CPU)."""
    for mod in ("attention", "int8_gemv", "fused_block_gemv"):
        monkeypatch.setattr(
            importlib.import_module(f"mxnet_tpu.ops.{mod}"), "on_tpu",
            lambda: True)


def _compile(fn, *args):
    """Compile for the described chip; returns the number of Pallas
    kernels (``tpu_custom_call``) in the optimized program."""
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


def _s(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("shape", [(B_TRAIN, H, T_TRAIN, HD),
                                   (1, H, 300, HD)],
                         ids=["train_16x12x1024x64", "prefill_T300"])
def test_flash_forward_compiles(one_chip, shape):
    att = importlib.import_module("mxnet_tpu.ops.attention")
    q = _s(one_chip, shape, jnp.bfloat16)
    n = _compile(lambda q, k, v: att._pallas_forward(q, k, v, True, 0.125),
                 q, q, q)
    assert n == 1


@pytest.mark.parametrize("shape,kernels", [
    ((B_TRAIN, H, T_TRAIN, HD), 1),      # the fused dq/dk/dv kernel
    ((1, H, 300, HD), 1),
    ((1, H, 16384, HD), 2),              # the two-kernel dq; dk+dv sweep
], ids=["train_fused", "prefill_T300_fused", "T16384_two_kernel"])
def test_flash_backward_compiles(one_chip, shape, kernels):
    att = importlib.import_module("mxnet_tpu.ops.attention")
    q = _s(one_chip, shape, jnp.bfloat16)
    tp = att._choose_block(shape[2])[1]
    lse = _s(one_chip, shape[:2] + (tp, 1), jnp.float32)
    n = _compile(
        lambda q, k, v, o, l, do: att._pallas_backward(
            q, k, v, o, l, do, True, 0.125), q, q, q, q, lse, q)
    assert n == kernels


# ------------------------------------------------------------------ the GEMVs
_GEMV_SHAPES = [(3 * D, D), (D, D), (4 * D, D), (D, 4 * D), (VP, D)]
_GEMV_IDS = ["qkv", "attn_out", "fc", "proj", "lm_head"]


@pytest.mark.parametrize("N,K", _GEMV_SHAPES, ids=_GEMV_IDS)
def test_int8_gemv_compiles(one_chip, as_tpu, N, K):
    gemv = importlib.import_module("mxnet_tpu.ops.int8_gemv")
    n = _compile(gemv.int8_weight_matmul,
                 _s(one_chip, (B_SERVE, K), jnp.bfloat16),
                 _s(one_chip, (N, K), jnp.int8),
                 _s(one_chip, (N,), jnp.float32))
    assert n == 1


@pytest.mark.parametrize("N,K", _GEMV_SHAPES, ids=_GEMV_IDS)
def test_int4_gemv_compiles(one_chip, as_tpu, N, K):
    gemv = importlib.import_module("mxnet_tpu.ops.int8_gemv")
    n = _compile(lambda x, w, s: gemv.int4_weight_matmul(x, w, s),
                 _s(one_chip, (B_SERVE, K), jnp.bfloat16),
                 _s(one_chip, (N, K // 2), jnp.uint8),
                 _s(one_chip, (N, K // 128), jnp.float32))
    assert n == 1


# ------------------------------------------------------------- the fused head
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "grammar"])
def test_fused_head_compiles(one_chip, masked):
    fb = importlib.import_module("mxnet_tpu.ops.fused_block_gemv")
    args = [_s(one_chip, (B_SERVE, D), jnp.bfloat16),
            _s(one_chip, (VP, D), jnp.int8),
            _s(one_chip, (VP,), jnp.float32),
            _s(one_chip, (B_SERVE,), jnp.float32),
            _s(one_chip, (B_SERVE,), jnp.uint32)]
    if masked:
        args.append(_s(one_chip, (B_SERVE, VP), jnp.bool_))

    def head(h, w, s, t, kb, mask=None):
        return fb._head_kernel(h, w, s, VOCAB, t, kb,
                               out_dtype=jnp.bfloat16, mask=mask)

    assert _compile(head, *args) == 1


# ------------------------------------------------------- the TrainStep body
def _gpt2_small_step(layers):
    """A GPT-2-small-width TrainStep (depth cut to ``layers``: the
    per-layer program repeats, and a 12-layer compile is chip_smoke.py's
    job) and the argument tuple of its jitted body."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import np, parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.models.gpt import GPT2_SMALL, GPTModel

    cfg = dataclasses.replace(GPT2_SMALL, num_layers=layers, dropout=0.0,
                              dtype=jnp.bfloat16)
    net = GPTModel(cfg)
    net.initialize()
    ids = np.array(onp.zeros((B_TRAIN, T_TRAIN), onp.int32))
    step = parallel.TrainStep(net, SoftmaxCrossEntropyLoss(),
                              mx.optimizer.Adam(learning_rate=1e-4),
                              example_inputs=[ids], donate=False)
    args = (tuple(step.model.values()), tuple(step._opt_states),
            ((ids._data,), (ids._data,)), jnp.float32(1e-4), jnp.int32(1),
            jnp.int32(1), jnp.float32(1.0))
    return step, args


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def test_trainstep_body_compiles(one_chip, as_tpu):
    """The whole fused step (forward, Pallas flash fwd+bwd per layer, Adam)
    at GPT-2-small width and B=16, T=1024 fits one v5e and keeps its
    kernels."""
    layers = 2
    step, args = _gpt2_small_step(layers)
    compiled = step._jitted.lower(*_shapes(args, one_chip)).compile()
    # one forward and one fused backward kernel per layer
    assert compiled.as_text().count("tpu_custom_call") == 2 * layers
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 16 * 1024 ** 3


def test_trainstep_body_compiles_data_parallel(topo, one_chip, as_tpu):
    """The same step over a dp=4 mesh of the described chips. GSPMD cannot
    partition a Mosaic kernel; the flash kernels map themselves over 'dp'
    (ops/attention._over_mesh) — without that this compile raises "Mosaic
    kernels cannot be automatically partitioned", as the first four-chip
    run of PR 23 did."""
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel.mesh import use_mesh

    layers = 2
    step, args = _gpt2_small_step(layers)
    mesh = Mesh(onp.array(topo.devices).reshape(4), ("dp",))
    repl, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    shapes = (_shapes(args[0], repl), _shapes(args[1], repl),
              _shapes(args[2], dp)) + _shapes(tuple(args[3:]), repl)
    with use_mesh(mesh):
        compiled = step._jitted.lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 * layers
    assert "all-reduce" in text          # the dp gradient reduction


# ------------------------------------- MiniCPM-SALA's two mixers, real widths
# the long-document cell's geometry: 16 slots, 2,560 pages of 64 (+ sink), a
# block table of 520 pages, 32 query heads over 2 KV heads of 128
@pytest.mark.parametrize("rows,T", [(16, 1), (1, 1024)],
                         ids=["decode_b16", "chunk_c1024"])
def test_sparse_paged_attention_compiles(one_chip, rows, T):
    """A decode step of 16 rows and a chunk of 1,024 positions, the pools
    donated: the chip keeps the ``[2561, 64, 256]`` K and V pools as
    declared (a position's two heads as one row of two lane tiles), no
    instruction copies a pool, nor the ``[163904, 256]`` view the chunk's
    scatter writes through, and every pool that goes in comes out as the
    same buffer. With ``[2561, 2, 64, 128]`` pools both programs copied
    each pool into the scatter's layout and back, aliased all the same, so
    that ``alias_size_in_bytes`` could not tell: ``count_pool_copies``,
    which ``stats()["pool_copies"]`` sums, read 6 in each. A CPU run cannot
    see this."""
    import re
    from mxnet_tpu.serve.engine import count_pool_copies
    sa = importlib.import_module("mxnet_tpu.ops.sparse_attention")
    bf, i32 = jnp.bfloat16, jnp.int32
    kv, kc = (2561, 64, 256), (2561, 4, 256)
    rows_in = _s(one_chip, (rows, T, 256), bf)
    compiled = jax.jit(
        lambda q, k, v, kp, vp, kcp, bt, pos, valid: sa.sparse_paged_attention(
            q, k, v, kp, vp, kcp, bt, pos, valid, rep=16,
            sc=sa.SparseConfig()),
        donate_argnums=(3, 4, 5)).lower(
        _s(one_chip, (rows, 32, T, 128), bf), rows_in, rows_in,
        _s(one_chip, kv, bf), _s(one_chip, kv, bf), _s(one_chip, kc, bf),
        _s(one_chip, (rows, 520), i32), _s(one_chip, (rows,), i32),
        _s(one_chip, (rows,), i32)).compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert re.findall(r"bf16\[2561,64,256\]\{([\d,]*)", entry) == ["2,1,0"] * 2
    copies = [line.strip()[:160] for line in text.splitlines()
              if _opcode(line)[0] in ("copy", "copy-start", "copy-done")
              and any(shape in _opcode(line)[1] for shape in (
                  "[2561,64,256]", "[163904,", "[10244,"))]
    assert not copies, copies
    # the compressed keys (5.2 MB) may be staged nearer in the 16-row step,
    # whose rows gather 17 MB of them: as declared, in another memory space
    staged = [_opcode(line) for line in text.splitlines()
              if "[2561,4,256]" in _opcode(line)[1]
              and _opcode(line)[0] in ("copy", "copy-start", "copy-done")]
    assert all(op != "copy" and shape.count("{2,1,0:T(4,128)(2,1)") ==
               shape.count("[2561,4,256]") for op, shape in staged), staged
    assert count_pool_copies(text, [kv, kv, kc]) == 0
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                        text).group(1)
    assert aliased.count("alias") == 3
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * 2561 * 64 * 256 * 2 + 2561 * 4 * 256 * 2


@pytest.mark.parametrize("rows,T", [(16, 1), (1, 1024)],
                         ids=["decode_b16", "chunk_c1024"])
def test_lightning_attention_compiles(one_chip, rows, T):
    la = importlib.import_module("mxnet_tpu.ops.linear_attention")
    x = _s(one_chip, (rows, 32, T, 128), jnp.bfloat16)
    i = _s(one_chip, (rows,), jnp.int32)
    _compile(
        lambda q, k, v, pool, slots, pos, valid: la.lightning_attention_slots(
            q, k, v, pool, slots, pos, la.decay_slopes(32, 16, 32), valid),
        x, x, x, _s(one_chip, (17, 32, 128, 128), jnp.float32), i, i, i)


# ------------------------------- the KV pools stay where they lie (PR 31)
# the chat cell's geometry (16 slots, 320 pages of 16 + the sink, chunks of
# 128) at two row widths: GPT-2-small's 768 lanes, six whole tiles of 128,
# and GPT-2 XL's 1,600, twelve and a half
_POOL_WIDTHS = {"small_768": (768, 12), "xl_1600": (1600, 25)}


@pytest.fixture(scope="module")
def pool_engines():
    """One single-layer paged engine a width, made on the CPU: its builders
    give the programs that the described chip compiles."""
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import InferenceEngine
    made = {}

    def engine(width):
        if width not in made:
            d, heads = _POOL_WIDTHS[width]
            net = GPTModel(GPTConfig(
                vocab_size=2048, hidden_size=d, num_layers=1,
                num_heads=heads, max_position_embeddings=1024, dropout=0.0,
                dtype="bfloat16"))
            net.initialize()
            made[width] = InferenceEngine(
                net, max_batch_size=16, max_len=1024, page_size=16,
                num_pages=320, prefill_chunk=128)
        return made[width]

    return engine


def _opcode(line):
    """The opcode of one instruction of an optimized HLO text, and the
    text of its result's shape."""
    import re
    m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(",
                 line)
    return (m.group(2), m.group(1)) if m else (None, "")


@pytest.mark.parametrize("program,bucket",
                         [("decode", 8), ("prefill", 64)],
                         ids=["step_b8", "prefill_b64"])
@pytest.mark.parametrize("width", list(_POOL_WIDTHS))
def test_pools_are_written_in_place(one_chip, pool_engines, width, program,
                                    bucket):
    """A decode step of 8 rows and a final prefill of 64 tokens, the pools
    donated: the chip keeps a ``[321, 16, kv_heads * head_dim]`` pool as
    declared, no instruction copies one, and every pool that goes in comes
    out as the same buffer. With ``[321, heads, 16, 64]`` pools the same
    programs relaid every pool out and back, donated or not: two thirds of
    a step (PERF.md section 6, PR 31). A CPU run cannot see this."""
    import re
    eng = pool_engines(width)
    build = eng._build_step if program == "decode" else eng._build_prefill
    compiled = build(bucket).lower(
        *_shapes(eng._example_args(program, bucket), one_chip)).compile()
    text = compiled.as_text()
    d = _POOL_WIDTHS[width][0]
    pool = f"bf16[321,16,{d}]"
    assert eng._pools[0].shape == (321, 16, d) and len(eng._pools) == 2
    # as declared: rows of d lanes, minor-most
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert re.findall(re.escape(pool) + r"\{([\d,]*)", entry) == ["2,1,0"] * 2
    copies = [line.strip()[:160] for line in text.splitlines()
              if _opcode(line)[0] in ("copy", "copy-start", "copy-done")
              and pool in _opcode(line)[1]]
    assert not copies
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                        text).group(1)
    assert aliased.count("alias") == 2
    # the bytes of both pools as the chip holds them (1,600 lanes pad to
    # 1,664), which is what stats()["pool_bytes_in_place"] reports there
    lanes = -(-d // 128) * 128
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * 321 * 16 * lanes * 2


# ------ a decoding row's walk multiplies a block as the pool stores it (PR 35)
# ------ and a chunk's is one kernel where a head is whole lane tiles (PR 37)
# (rows, heads, query heads a KV head, head width, T, pool, pages a row,
# window): the conversation cell's group of EvaByte heads at 8 rows, the chat
# cell's GPT-2 XL at 2, the conversation cell's chunk, the document cell's
# windowed chunk (Command A+: 8 KV heads, rows of 1,024 lanes), the chat
# cell's chunk
_WALKS = {
    "evabyte_step_b8": (8, 16, 1, 128, 1, (193, 128, 2048), 32, None),
    "xl_step_b2": (2, 25, 1, 64, 1, (321, 16, 1600), 64, None),
    "evabyte_chunk_c1024": (1, 16, 1, 128, 1024, (193, 128, 2048), 32, None),
    "commandaplus_chunk_c1024": (1, 128, 16, 128, 1024, (4161, 128, 1024),
                                 260, 4096),
    "xl_chunk_c128": (1, 25, 1, 64, 128, (321, 16, 1600), 64, None),
}
# sha1 over "opcode shape" of every instruction of the XL chunk's loop body,
# as the compiler gave it at the parent of PR 37: a head of 64 lanes keeps the
# head-split form, and a change to it (models/llama._walk_pages) shows here
_XL_CHUNK_BODY = "f2d0947de9bf973ce19b20034deab4d5028dd653"


@pytest.fixture
def walk_on_tpu(monkeypatch):
    """``models/llama.walk_form`` answers as on a TPU (it asks ``on_tpu()``,
    which sees this process's CPU); traces made before are dropped, so that
    the jitted ``_paged_attention`` is traced anew."""
    from mxnet_tpu.models import llama
    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _loop_body(text):
    """[(opcode, result shapes as text)] of the instructions in the body of
    the one ``while`` of an optimized HLO text."""
    import re
    (name,) = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if re.match(rf"%?{re.escape(name)} \(", ln))
    body = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            break
        rest = ln.split(" = ", 1)[1]
        depth = 0
        for end, ch in enumerate(rest):       # a tuple's shape has spaces
            depth += (ch == "(") - (ch == ")")
            if ch == " " and not depth:
                break
        body.append((rest[end + 1:].split("(", 1)[0], rest[:end]))
    return body


@pytest.mark.parametrize("name", list(_WALKS))
def test_walk_compiles_in_its_form(one_chip, walk_on_tpu, name):
    """With few query columns (a step's heads x 1) the loop of the paged
    read is two gathers of a block as the pool lies and two matrix products
    over them: no instruction of its body makes a float32 array of a block's
    size, as the head-split form's ``convert`` and ``copy`` (EvaByte: 8 MiB
    each, for K and again for V) and its ``reshape f32[2,128,25,64]`` (GPT-2
    XL) did in every trip. Bfloat16 operands alone do not do it: a one-row
    product stays on the vector unit, which has no bfloat16 (PERF.md section
    6, PR 35). A chunk whose head is whole lane tiles is one Pallas kernel
    (``ops/paged_prefill.py``): Mosaic takes its DMAs of a head's lanes and
    its VMEM budget, and no XLA loop is left to carry the float32
    accumulator of ``[G, rep, T, hd]`` through HBM (PERF.md section 6, PR
    37). GPT-2 XL's chunk (heads of 64 lanes) keeps the head-split form,
    whose loop is what it was. A CPU run cannot see any of this."""
    import hashlib
    import re
    from mxnet_tpu.models.llama import _paged_attention, walk_form
    rows, heads, rep, hd, T, pool, maxp, window = _WALKS[name]
    bf, i32 = jnp.bfloat16, jnp.int32
    x = _s(one_chip, (rows, heads, T, hd), bf)
    kv = _s(one_chip, (rows, heads // rep, T, hd), bf)
    compiled = jax.jit(
        lambda q, k, v, kp, vp, bt, pos: _paged_attention(
            q, k, v, kp, vp, bt, pos, rep, window=window),
        donate_argnums=(3, 4)).lower(
        x, kv, kv, _s(one_chip, pool, bf), _s(one_chip, pool, bf),
        _s(one_chip, (rows, maxp), i32), _s(one_chip, (rows,), i32)).compile()
    text = compiled.as_text()
    form = walk_form(heads, T, hd)
    if form == "kernel":
        assert text.count("tpu_custom_call") == 1 and " while(" not in text
        carry = rows * heads * T * hd
        wide = [dims for dims in re.findall(r"f32\[([\d,]+)\]", text)
                if math.prod(int(d) for d in dims.split(",")) >= carry]
        assert not wide
        return
    body = _loop_body(text)
    if T > 1:
        assert form == "heads"
        assert hashlib.sha1("\n".join(
            f"{op} {shape}" for op, shape in body).encode()).hexdigest() \
            == _XL_CHUNK_BODY
        return
    assert form == "lanes"
    block = rows * 128 * pool[2]
    gathered = f"bf16[{block // (pool[1] * pool[2])},{pool[1]},{pool[2]}]"
    assert sum(shape.startswith(gathered) for _, shape in body) == 2
    wide = [(op, shape[:80]) for op, shape in body
            for dims in re.findall(r"f32\[([\d,]+)\]", shape)
            if math.prod(int(d) for d in dims.split(",")) >= block]
    assert not wide
    assert " convolution(" in text


# ------------- the tail of a serving program: no sort, no table relaid out
# one GPT-2 layer over the whole vocabulary at two widths: GPT-2 XL's 1,600
# lanes (12.5 tiles) and GPT-2 medium's 1,024 (8 tiles)
_TAIL_WIDTHS = {"xl_1600": (1600, 25), "medium_1024": (1024, 16)}


@pytest.fixture(scope="module")
def tail_engines():
    from mxnet_tpu.models import GPTModel
    from mxnet_tpu.models.gpt import GPTConfig
    from mxnet_tpu.serve import InferenceEngine
    made = {}

    def engine(width):
        if width not in made:
            d, heads = _TAIL_WIDTHS[width]
            net = GPTModel(GPTConfig(
                vocab_size=VOCAB, hidden_size=d, num_layers=1,
                num_heads=heads, max_position_embeddings=1024, dropout=0.0,
                dtype="bfloat16"))
            net.initialize()
            made[width] = InferenceEngine(
                net, max_batch_size=16, max_len=1024, page_size=16,
                num_pages=320, prefill_chunk=128)
        return made[width]

    return engine


@pytest.mark.parametrize("program,bucket",
                         [("decode", 2), ("prefill", 64)],
                         ids=["step_b2", "prefill_b64"])
@pytest.mark.parametrize("width", list(_TAIL_WIDTHS))
def test_tail_neither_sorts_nor_relays_a_table(one_chip, tail_engines, width,
                                               program, bucket):
    """A decode step of 2 rows and a final prefill of 64 tokens: no ``sort``
    (``filter_logits`` searches for its thresholds; ``lax.top_k`` would be
    the same sort here), and no instruction makes a second array of the
    embedding's shape. The chip holds a ``[50257, 1600]`` table vocabulary
    minor-most whatever reads it: the head's product reads it so, and the
    embedding gathers from rows padded to 1,664 lanes, which the chip keeps
    as declared. With one table for both, the gather relaid 160 MB out in
    every program (PERF.md section 6, PR 33). At 1,024 lanes the table is
    kept as declared and the engine holds no second one. A CPU run cannot
    see either fact."""
    import re
    eng = tail_engines(width)
    d = _TAIL_WIDTHS[width][0]
    lanes = -(-d // 128) * 128
    held = [tuple(v.shape) for v in eng._values
            if v.ndim == 2 and v.shape[0] == VOCAB]
    assert held == ([(VOCAB, d), (VOCAB, lanes)] if lanes != d
                    else [(VOCAB, d)])
    build = eng._build_step if program == "decode" else eng._build_prefill
    text = build(bucket).lower(
        *_shapes(eng._example_args(program, bucket), one_chip)
    ).compile().as_text()
    ops = [_opcode(line) for line in text.splitlines()]
    assert not [shape for op, shape in ops if op == "sort"]
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    layouts = dict(re.findall(r"(bf16\[%d,\d+\])\{([\d,]*)" % VOCAB, entry))
    # the table goes into the head's product and into the gather as it
    # came: nothing but a parameter has a table's shape, or a bitcast, which
    # moves nothing, or a prefetch, which keeps the layout
    tables = [f"bf16[{VOCAB},{d}]", f"bf16[{d},{VOCAB}]",
              f"bf16[{VOCAB},{lanes}]"]
    made = []
    for line in text.splitlines():
        op, shape = _opcode(line)
        if (op in (None, "parameter", "bitcast", "get-tuple-element")
                or not any(t in shape for t in tables)
                or (op == "fusion" and "calls=%bitcast_fusion" in line)):
            continue
        laid = re.findall(r"(bf16\[[\d,]+\])\{([\d,]*)", shape)
        if op in ("copy-start", "copy-done") and all(
                layouts.get(t) == lay for t, lay in laid):
            continue
        made.append(line.strip()[:160])
    assert not made
    if lanes != d:
        # by its shape, not by its use: vocabulary minor-most, and the
        # padded rows as declared
        assert layouts == {f"bf16[{VOCAB},{d}]": "0,1",
                           f"bf16[{VOCAB},{lanes}]": "1,0"}
    else:
        assert layouts == {f"bf16[{VOCAB},{d}]": "1,0"}


# ---------------- EvaByte's folding programs at the published widths (PR 34)
@pytest.fixture(scope="module")
def evabyte_engine():
    """One EvaByte layer at the published widths (hidden 4,096, 32 heads of
    128, MLP 11,008, windows of 2,048 in chunks of 16) behind the
    conversation cell's engine geometry, but for the pool's size: 32 pages of
    128 rows, the least that ``max_len`` 32,768 allows, so that the CPU holds
    70 MB of pools and not 6 GB. Made on the CPU: its builders give the
    programs that the described chip compiles."""
    from mxnet_tpu.models.evabyte import EvaByteConfig, EvaByteForCausalLM
    from mxnet_tpu.serve import InferenceEngine
    net = EvaByteForCausalLM(EvaByteConfig(num_layers=1))
    net.initialize()
    return InferenceEngine(net, max_batch_size=16, max_len=32768,
                           page_size=128, num_pages=32, prefill_chunk=2048,
                           min_prompt_bucket=256, prefix_cache=False)


@pytest.mark.parametrize("program,bucket",
                         [("decode", 16), ("chunk", 2048), ("prefill", 256)],
                         ids=["step_b16", "chunk_c2048", "prefill_b256"])
def test_evabyte_programs_fold_in_place(one_chip, evabyte_engine,
                                        walk_on_tpu, program, bucket):
    """The decode step of 16 rows, a middle chunk of 2,048 positions (a whole
    window) and a last chunk of 256. Whether a dispatch ends a window is data (``pos +
    valid`` reaching a multiple of 2,048), so the chunk that ends one and the
    chunk that does not are ONE program, and so are the two steps: each holds
    the summarising loop (``mx.eva_summarize``), which makes no trip where no
    row folds. The chip's compiler takes them at the published widths; the
    table is 32 entries wide; and the donated pools come out as the buffers
    that went in: no instruction copies a pool, and the aliased bytes are the
    pools' bytes. A CPU run cannot see the last fact."""
    eng = evabyte_engine
    assert eng.maxp == 15 + 16 + 1
    build = {"decode": eng._build_step, "chunk": eng._build_chunk,
             "prefill": eng._build_prefill}[program]
    # the programs are traced from shapes: the pools get the cell's own 192
    # pages and the sink here (the compiler treats a pool of 17 MB otherwise:
    # it prefetches all of it into its nearer memory)
    args = list(_shapes(eng._example_args(program, bucket), one_chip))
    args[1] = tuple(_s(one_chip, (193, 128, 2048), jnp.bfloat16)
                    for _ in args[1])
    compiled = build(bucket).lower(*args).compile()
    text = compiled.as_text()
    assert "mx.eva_summarize" in text
    # (a middle chunk's logits are not used, so with ONE layer its walk is
    # dead code: only the writes and the fold are left of its attention); a
    # last chunk's walk is the kernel (PR 37)
    assert "mx.kv_walk" in text or program == "chunk"
    assert ("tpu_custom_call" in text) == (program == "prefill")
    # 32 heads of 128 in two groups of 16: rows of 2,048 lanes. From rows of
    # 4,096 the compiler's gather of 16 rows' pages sliced the whole pool in
    # halves ("mini-gather-slice") in every trip of the walk (PR 34)
    pool = "bf16[193,128,2048]"
    assert [tuple(p.shape) for p in eng._pools] == [(33, 128, 2048)] * 4
    assert "mini-gather" not in text
    copies = [line.strip()[:160] for line in text.splitlines()
              if _opcode(line)[0] in ("copy", "copy-start", "copy-done")
              and pool in _opcode(line)[1]]
    assert not copies
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 4 * 193 * 128 * 2048 * 2


# ------- Command A+'s programs at the published widths (PR 36): experts that
# ------- are told what they hold, pools of two kinds
@pytest.fixture(scope="module")
def cohere_engine():
    """Two layers of Cohere2-MoE at the published widths (hidden 4,096, 128
    query heads over 8 KV heads of 128, experts of width 4,096, 4 shared, a
    router over 128, a window of 4,096), one sliding and one full, holding 2
    of the 128 routed experts and a vocabulary of 2,048 rows, so that the CPU
    holds 1.8 GB of weights and not 9.5; behind the long-document cell's
    engine geometry, but for the full kind's pool. Made on the CPU: its builders
    give the programs that the described chip compiles."""
    from mxnet_tpu.models.cohere2_moe import (Cohere2MoEConfig,
                                              Cohere2MoEForCausalLM)
    from mxnet_tpu.serve import InferenceEngine
    net = Cohere2MoEForCausalLM(Cohere2MoEConfig(
        vocab_size=2048, num_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        experts_held=(0, 2)))
    net.initialize()
    return InferenceEngine(net, max_batch_size=16, max_len=33280,
                           page_size=128, num_pages=260, prefill_chunk=1024,
                           min_prompt_bucket=512, prefix_cache=False)


@pytest.mark.parametrize("program,bucket",
                         [("decode", 16), ("chunk", 1024), ("prefill", 512)],
                         ids=["step_b16", "chunk_c1024", "prefill_b512"])
def test_cohere2_moe_programs_fit_and_copy_nothing(one_chip, cohere_engine,
                                                   walk_on_tpu, program,
                                                   bucket):
    """The decode step of 16 rows, a middle chunk of 1,024 positions and a
    last chunk of 512, with the cell's own pools (4,160 pages of the full
    kind, 656 of the windowed, and their sinks). The chip's compiler takes
    them; the donated pools of both kinds come out as the buffers that went
    in; no expert's matrices are copied to be multiplied (the held experts'
    stacked weights are sliced where they lie, inside the loop whose trip
    count follows the routing); and what the program needs beside its
    arguments, added to the whole cut's 9.47 GB of weights and the cell's
    pools, is inside the chip's 16 GiB. A CPU run cannot see any of it."""
    eng = cohere_engine
    assert eng._wpages.layout.held_bound == 41 and eng.maxp == 260
    build = {"decode": eng._build_step, "chunk": eng._build_chunk,
             "prefill": eng._build_prefill}[program]
    args = list(_shapes(eng._example_args(program, bucket), one_chip))
    pages = {0: 4161, 1: 657}
    args[1] = tuple(_s(one_chip, (pages[kind], 128, 1024), jnp.bfloat16)
                    for kind in eng.model.cache_kinds())
    compiled = build(bucket).lower(*args).compile()
    text = compiled.as_text()
    for scope in ("mx.moe_route", "mx.moe_experts", "mx.moe_shared",
                  "mx.kv_walk", "mx.kv_write"):
        assert scope in text, scope
    # a chunk's and a prefill's walk is the kernel in each layer whose output
    # is used (a middle chunk's last layer feeds no logits), a step's the
    # lanes form (PR 37)
    assert text.count("tpu_custom_call") \
        == {"decode": 0, "chunk": 1, "prefill": 2}[program]
    pools = 2 * (4161 + 657) * 128 * 1024 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pools
    big = [line.strip()[:160] for line in text.splitlines()
           if _opcode(line)[0] in ("copy", "copy-start", "copy-done")
           and any(shape in _opcode(line)[1] for shape in (
               "[4161,128,1024]", "[657,128,1024]", "[2,4096,4096]",
               "[4096,4096]", "[16384,4096]", "[4096,16384]"))]
    assert not big, big
    # the whole cut: 4 layers, 16 held experts, 32,768 rows of vocabulary;
    # pools: one full layer's and three windowed ones'
    weights = 2 * 4_733_292_544
    cell_pools = 2 * (4161 + 3 * 657) * 128 * 1024 * 2
    assert weights + cell_pools + mem.temp_size_in_bytes < 0.95 * 16 * 2 ** 30


# ---------- the programs that bypass the kernel lower as they did (PR 37)
# sha1 of ``lower().as_text()`` at the parent of PR 37, with ``walk_form``
# answering as on a TPU: the chat cell's programs (GPT-2 XL's heads of 64
# lanes keep the head-split form in a chunk and a bucket), and every decode
# step of the three serving geometries (a step keeps the lanes form)
_PARENT_TEXT = {
    ("xl", "decode", 1): "cef46a897cd068f6244af71dd51fa8ce947e3485",
    ("xl", "decode", 2): "74cdf0a1fd6acf08ee0e043cc4d9f602594a85ff",
    ("xl", "decode", 4): "f7ae4763b5f885cadeea79a047def080773ae3e4",
    ("xl", "decode", 8): "c93b1ae4754963011daa242b67e9ad279b0c76f1",
    ("xl", "decode", 16): "780e6d7eb45ae7d3fcb8bff7dc95b428d1ab545a",
    ("xl", "prefill", 8): "8036dac223609863ae053b5a1497048370f331ba",
    ("xl", "prefill", 16): "a2c8f6b0e3e04e9efcaed1f3a7a75e4e08ad5e87",
    ("xl", "prefill", 32): "3050aeb967880a65c4c02e43be3457297a21570b",
    ("xl", "prefill", 64): "af97dceea7db13c30d3b22fe7a19da5feabc6164",
    ("xl", "prefill", 128): "7b52c1ea41ea9d61e08d5e0b4fa19246bccbbfd5",
    ("xl", "chunk", 128): "828ab2309a7703dfe1bc3a068280c4108b3bd76b",
    ("evabyte", "decode", 1): "bc3e95a504fc7b803b40fd23850c245a9f9c5b30",
    ("evabyte", "decode", 2): "c2dbb7ccae2d5713542eda6e986af63575838e24",
    ("evabyte", "decode", 4): "727106165587f918e4e6c8a880a1ace68a37019a",
    ("evabyte", "decode", 8): "cc27c7513ece935ada3e32ab1346437ad903865e",
    ("evabyte", "decode", 16): "93f24da883324c7fbe516f685d98332d4ed8be76",
    ("cohere", "decode", 1): "3db5a3093b003e8bf0371010696412f5fbd867b5",
    ("cohere", "decode", 2): "e68d7c721c02a82b4d3a2f01d8244441a2d88535",
    ("cohere", "decode", 4): "6b9861caa9f5f88f85cb2f3b7170fd0d50f09088",
    ("cohere", "decode", 8): "9cedf70e2a8c951d12617a2c4eb02c738249f21c",
    ("cohere", "decode", 16): "20525df2151e07ca06162499e31dc61c44c76848",
}


@pytest.mark.parametrize("geometry, program, bucket", list(_PARENT_TEXT),
                         ids=[f"{g}-{p}-{b}" for g, p, b in _PARENT_TEXT])
def test_programs_that_bypass_the_kernel_lower_as_before(
        request, one_chip, walk_on_tpu, geometry, program, bucket):
    import hashlib
    if geometry == "xl":
        eng = request.getfixturevalue("pool_engines")("xl_1600")
    else:
        eng = request.getfixturevalue(f"{geometry}_engine")
    build = {"decode": eng._build_step, "chunk": eng._build_chunk,
             "prefill": eng._build_prefill}[program]
    args = list(_shapes(eng._example_args(program, bucket), one_chip))
    if geometry == "evabyte":
        args[1] = tuple(_s(one_chip, (193, 128, 2048), jnp.bfloat16)
                        for _ in args[1])
    elif geometry == "cohere":
        pages = {0: 4161, 1: 657}
        args[1] = tuple(_s(one_chip, (pages[kind], 128, 1024), jnp.bfloat16)
                        for kind in eng.model.cache_kinds())
    text = build(bucket).lower(*args).as_text()
    assert hashlib.sha1(text.encode()).hexdigest() \
        == _PARENT_TEXT[(geometry, program, bucket)]
