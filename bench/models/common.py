"""What every family's builder does the same way: the type the weights are
kept in, the seed's weights in one jitted call (as the reference's tree, or
laid out under the program's parameter names), and the installation of those
arrays in the program's model."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype_served", "bfloat16"))


def from_tree(tree, names, leaf_of):
    """{program parameter name: array} from the reference's ``tree`` (top
    leaves and stacked ``"layers"``), by the builder's ``leaf_of``."""
    out = {}
    for name in names:
        leaf, layer, transposed = leaf_of(name)
        x = tree[leaf] if layer is None else tree["layers"][leaf][layer]
        out[name] = x.T if transposed else x
    return out


@functools.lru_cache(maxsize=None)
def _weights_fn(ref, key, names, leaf_of):
    cfg = dict(key)

    def make(words):
        tree = ref.init_params(cfg, (words[0], words[1]), dtype_of(cfg))
        return tree if names is None else from_tree(tree, names, leaf_of)

    return jax.jit(make)


def seeded(ref, cfg: dict, seed: int, names=None, leaf_of=None):
    """The seed's weights on the default device, made in one jitted call, in
    the type they are served in: the reference ``ref``'s own tree, or with
    ``names`` ``{program parameter name: array}``."""
    fn = _weights_fn(ref, ref.cfg_key(cfg), names and tuple(names), leaf_of)
    return fn(np.asarray(ref.seed_words(seed)))


def install(net, weights, train: bool):
    """Put ``weights`` into the program's model, refusing where names or
    shapes differ. For serving the parameters carry no gradient buffer
    (``grad_req='null'``, MXNet's own idiom for inference): the eager buffer
    would double the weights' memory for nothing."""
    params = net.collect_params()
    missing = set(params) ^ set(weights)
    if missing:
        raise SystemExit(f"bench: parameter names differ between the "
                         f"program and the builder: {sorted(missing)[:6]}")
    for name, p in params.items():
        w = weights[name]
        if tuple(p.shape) != tuple(w.shape):
            raise SystemExit(f"bench: {name}: program shape {p.shape}, "
                             f"reference shape {w.shape}")
        if not train:
            p.grad_req = "null"
        p.set_data(w)
    return net
