"""The plain reference: the published architecture in float32.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, no kernel, no
cache, no batching: one sequence at a time, one layer at a time, with
causal softmax attention over the whole sequence (in query blocks, so
that long sequences fit).  It follows the published models: pre-norm
blocks (LayerNorm with bias, or RMSNorm), attention with optional QKV
bias and grouped KV heads, rotary embedding on the first
``rotary_dim`` channels of each head in the rotate-half form, a SwiGLU
MLP, a final norm and an untied LM head.

The weights are read from the program's parameter layout (made by
``bench/weights.py``, not by the program), as a checkpoint loader
would read them.  That layout pairs rotary channels as (2i, 2i+1);
the published form pairs (i, i + rotary_dim/2).  The loader therefore
permutes the query and key channels of each head, which leaves every
attention score as it was.

``mode="fp8"`` is the control: the same computation with every matmul
operand (activations per row, weights per output column, and q, k, v
and the softmax weights) rounded to float8 e4m3 with a scale, the step
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 256


def half_split_order(head_dim: int, rotary_dim: int) -> np.ndarray:
    """Channel order that turns (2i, 2i+1) rotary pairs into
    (i, i + rotary_dim/2) pairs."""
    return np.asarray(list(range(0, rotary_dim, 2))
                      + list(range(1, rotary_dim, 2))
                      + list(range(rotary_dim, head_dim)), np.int32)


def fp8(x, axis: int):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the scale maps the slice's largest magnitude to 448)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, mode):
    if mode == "fp8":
        a = fp8(a, -1)
        w = fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _norm(arch, p, x):
    if arch.norm == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + arch.norm_eps) * p["scale"] \
            + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + arch.norm_eps) * p["scale"]


def _rope(arch, x, pos):
    """Rotate-half rotary embedding; x (L, heads, hd), pos (L,)."""
    rd = arch.rotary_dim
    inv = 1.0 / (arch.rope_theta ** (jnp.arange(0, rd, 2,
                                                dtype=jnp.float32) / rd))
    ang = pos[:, None].astype(jnp.float32) * inv[None]       # (L, rd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xr = x[..., :rd]
    rot = jnp.concatenate([-xr[..., rd // 2:], xr[..., :rd // 2]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., rd:]], -1)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(arch, mode, units, i, x):
    """Layer ``i`` of the stacked unit parameters over x (Lp, D)."""
    p = _f32(jax.tree_util.tree_map(lambda a: a[i], units)["layers"][0])
    Lp = x.shape[0]
    H, KV, hd = arch.n_heads, arch.n_kv, arch.head_dim
    rep = H // KV
    a = p["attn"]
    h = _norm(arch, p["norm1"], x)
    q, k, v = (_mm(h, a["wq"], mode), _mm(h, a["wk"], mode),
               _mm(h, a["wv"], mode))
    if arch.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    order = half_split_order(hd, arch.rotary_dim)
    pos = jnp.arange(Lp)
    q = _rope(arch, q.reshape(Lp, H, hd)[..., order], pos)
    k = _rope(arch, k.reshape(Lp, KV, hd)[..., order], pos)
    v = v.reshape(Lp, KV, hd)
    if mode == "fp8":
        q, k, v = fp8(q, -1), fp8(k, -1), fp8(v, 0)

    def block(args):
        qb, q0 = args                                 # (Q, H, hd)
        qg = qb.reshape(qb.shape[0], KV, rep, hd)
        s = jnp.einsum("qgrd,kgd->grqk", qg, k,
                       precision=HIGHEST) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[0])
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if mode == "fp8":
            w = fp8(w, -1)
        o = jnp.einsum("grqk,kgd->qgrd", w, v, precision=HIGHEST)
        return o.reshape(qb.shape[0], H * hd)

    nb = Lp // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, H, hd),
                            jnp.arange(nb) * Q_BLOCK))
    x = x + _mm(o.reshape(Lp, H * hd), a["wo"], mode)
    m = p["mlp"]
    h = _norm(arch, p["norm2"], x)
    g = jax.nn.silu(_mm(h, m["w_gate"], mode)) * _mm(h, m["w_up"], mode)
    return x + _mm(g, m["w_down"], mode)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(arch, mode, final_norm, head, x, positions):
    xs = _norm(arch, _f32(final_norm), x[positions])
    return _mm(xs, head.astype(jnp.float32).T, mode)


def logits(arch, params, seq: Sequence[int], positions: Sequence[int],
           pad_to: int, n_positions: int,
           mode: Optional[str] = None) -> np.ndarray:
    """Reference logits (len(positions), vocab) of ``seq`` at
    ``positions``.  The sequence is padded at its end to ``pad_to``
    tokens (a multiple of the query block), which no earlier position
    attends to, and the positions to ``n_positions``, so that a cell
    compiles a few shapes only."""
    n = len(positions)
    if len(seq) > pad_to or n > n_positions or pad_to % Q_BLOCK:
        raise ValueError(f"sequence {len(seq)} / positions {n} exceed "
                         f"the padded shapes {pad_to} / {n_positions}")
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    pos = np.full(n_positions, positions[-1], np.int32)
    pos[:n] = positions
    x = _embed(params["embed"], toks)
    for i in range(arch.n_layers):
        x = _layer(arch, mode, params["units"], i, x)
    head = params["embed"] if arch.tie_embeddings else params["lm_head"]
    out = _head(arch, mode, params["final_norm"], head, x, pos)
    return np.asarray(out)[:n]
