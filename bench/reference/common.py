"""What the plain references of every family share, none of it about an
architecture: the seed's two words, a configuration as a static argument, a
draw, the rounding of a matmul operand that makes a *control*, and the gaps of
served tokens given the family's ``logits``. Imports nothing of the program.

Arithmetic is float32 with ``precision="highest"`` on every contraction (a
TPU otherwise multiplies float32 in one bfloat16 pass). ``fake`` names a
lower precision for the control: every matmul operand is rounded to that type
first, which is what serving or training "in fp8" would do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def cfg_key(cfg: dict):
    """The configuration's scalars as a hashable key (a static argument)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def draw(key, shape, std, mean, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return (x + jnp.float32(mean)).astype(dtype)


def seed_words(seed: int):
    """``seed`` as two uint32 words: seeds run past 2**31, and no cast may
    wrap two of them onto one key."""
    seed = int(seed)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def round_to(x, fake):
    """``x`` as a matmul operand: float32, or rounded through ``fake``. The
    rounding is straight-through: the backward pass sees the identity, so a
    lower-precision *forward* is what the control measures (a cast's own
    gradient would be rounded to ``fake`` too, and fp8 without loss scaling
    flushes every small gradient to zero: a crash, not a reading)."""
    x = x.astype(jnp.float32)
    if fake is None:
        return x
    if fake == "int8":
        # symmetric int8 with one scale per row, the usual scheme
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        low = jnp.round(x / s) * s
    else:
        low = x.astype(fake).astype(jnp.float32)
    return x + jax.lax.stop_gradient(low - x)


def mm(a, b, fake):
    return jnp.matmul(round_to(a, fake), round_to(b, fake), precision=HI)


# ---------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("logits", "cfg_key", "fake"))
def _gap_rows(logits, params, ids, cfg_key, fake):
    """For every position of ``ids`` the gap by which the reference's logit
    of the token judged there lies below the reference's best: the token
    judged is the next of ``ids`` (the served one) or, with ``fake``, the one
    the lower precision puts first. One shape whatever the lengths, so one
    program serves every sequence of every run."""
    cfg = dict(cfg_key)
    ref = logits(params, ids, cfg, None)
    if fake is None:
        pick = jnp.roll(ids, -1, axis=-1)
    else:
        pick = jnp.argmax(logits(params, ids, cfg, fake), axis=-1)
    got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return jnp.max(ref, axis=-1) - got


def served_gaps(logits, params, seqs, prompt_lens, cfg: dict, fake=None,
                pad_to=None):
    """With the family's ``logits(params, ids, cfg, fake)``: for each
    sequence (prompt + served tokens) the gaps, one per served token, by
    which the served token's reference logit lies below the reference's best
    at that position. With ``fake`` the token judged is
    not the served one but the one the lower precision puts first (the
    control). Sequences are padded to one length, so one program serves
    them all; causal attention keeps the padding out of what is read."""
    key = cfg_key(cfg)
    pad_to = pad_to or max(len(s) for s in seqs)
    out = []
    for seq, n_prompt in zip(seqs, prompt_lens):
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :len(seq)] = seq
        gaps = np.asarray(_gap_rows(logits, params, ids, key, fake))
        # the token at position i was chosen from the logits at i - 1
        out.append([float(g) for g in gaps[0, n_prompt - 1:len(seq) - 1]])
    return out
