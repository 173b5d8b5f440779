"""Random weights from a seed, in the program's parameter layout.

The benchmark makes the weights itself, so that the reference takes
nothing the program made.  The tree's structure and dtypes come from
the program's own initializer under ``jax.eval_shape`` (nothing is
computed there); every leaf is then filled in one jitted call on the
device, by the configuration's family's rule for it where the family
has one, and otherwise by a shared rule keyed on the leaf's name.
Biases and norm parameters are random too: zeros and ones would leave
those paths unchecked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

EMBED_STD = 0.02
BIAS_STD = 0.2
NORM_STD = 0.1
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


def _fill(path, leaf: jax.ShapeDtypeStruct, key, family_fill) -> jax.Array:
    x = family_fill(path, leaf, key)
    if x is not None:
        return x.astype(leaf.dtype)
    name = leaf_name(path)
    parent = leaf_name(path[:-1]) if len(path) > 1 else ""
    shape, dtype = leaf.shape, leaf.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        x = z * EMBED_STD
    elif name in MATRICES:
        # stacked unit weights are (units, fan_in, fan_out); the LM
        # head is (vocab, d_model) and reads d_model
        fan_in = shape[-1] if name == "lm_head" else shape[-2]
        x = z / math.sqrt(fan_in)
    elif name in ("bq", "bk", "bv"):
        x = z * BIAS_STD
    elif name == "scale" and parent.startswith(("norm", "final_norm")):
        x = 1.0 + z * NORM_STD
    elif name == "bias" and parent.startswith(("norm", "final_norm")):
        x = z * NORM_STD
    else:
        raise ValueError(f"no weight rule for parameter "
                         f"{jax.tree_util.keystr(path)}")
    return x.astype(dtype)


def make_params(cfg, seed: int, device, family_fill):
    """The program's parameter tree for ``cfg``, random from ``seed``,
    made on ``device`` in one jitted call.  ``family_fill`` is the
    family's ``fill``: a leaf's value, or ``None`` for the shared
    rules."""
    from repro.models import lm
    key = seed_key(seed)
    shapes = jax.eval_shape(functools.partial(lm.init_params, cfg=cfg), key)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        keys = jax.random.split(k, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [_fill(p, leaf, keys[i], family_fill)
                      for i, (p, leaf) in enumerate(paths)])

    return jax.jit(build, out_shardings=SingleDeviceSharding(device))(key)
