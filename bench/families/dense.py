"""The dense family: pre-norm blocks of attention over every key and a
SwiGLU MLP.  A configuration file that names no ``family`` is dense.

A family module gives the harness what depends on the architecture:

- ``load(path)``: the configuration file as a frozen, hashable arch
  (the reference passes it to ``jit`` as a static argument) with
  ``name``, ``registry``, ``vocab``, ``n_layers`` and
  ``program_config()``, an instance of the family's own ``Arch`` class,
  by whose module ``bench/lookup.py`` finds the family again (a family
  derived from this one subclasses ``Arch``);
- ``fill(path, leaf, key)``: the family's weight rule for a leaf, or
  ``None`` for the shared rules of ``bench/weights.py``;
- ``Q_BLOCK`` and ``logits(...)``: the plain reference in float32, with
  the float8 control;
- ``prefill_flops``, ``decode_flops``, ``decode_attn_work``: the work a
  token costs.

The configuration file holds the published config's keys, with every
key changed from the source listed in ``reduced``.  ``registry`` names
the program's own configuration and ``overrides`` the fields the
benchmark changes in it, each with its reason.  ``program_config``
applies them and then checks every size the program will run against
the file, so the file is the configuration as it is run.

The reference is straightforward ``jax.numpy`` at ``Precision.HIGHEST``,
no kernel, no cache, no batching: one sequence at a time, one layer at
a time, with causal softmax attention over the whole sequence (in query
blocks, so that long sequences fit).  It follows the published models:
pre-norm blocks (LayerNorm with bias, or RMSNorm), attention with
optional QKV bias and grouped KV heads, rotary embedding on the first
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

import dataclasses
import functools
import json
import math
from pathlib import Path
from typing import Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORMS = {"layernorm": "ln", "rmsnorm": "rms"}


# ---------------------------------------------------------------------- #
# the configuration                                                      #
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    registry: str
    d_model: int
    d_ff: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    rotary_pct: float
    rope_theta: float
    norm: str              # layernorm | rmsnorm
    norm_eps: float
    qkv_bias: bool
    tie_embeddings: bool
    overrides: tuple       # ((program field, value), ...)

    @classmethod
    def load(cls, path: Path) -> "Arch":
        d = json.loads(Path(path).read_text())
        if d.get("hidden_act") != "silu":
            raise ValueError(f"{path}: only SwiGLU (silu) MLPs are "
                             f"modelled, got {d.get('hidden_act')!r}")
        H = int(d["num_attention_heads"])
        norm = d["norm"]
        eps = d["layer_norm_eps"] if norm == "layernorm" \
            else d["rms_norm_eps"]
        return cls(
            name=d["name"], registry=d["registry"],
            d_model=int(d["hidden_size"]),
            d_ff=int(d["intermediate_size"]),
            n_layers=int(d["num_hidden_layers"]), n_heads=H,
            n_kv=int(d["num_key_value_heads"]),
            # published where the heads are not hidden_size / heads wide
            head_dim=int(d.get("head_dim") or int(d["hidden_size"]) // H),
            vocab=int(d["vocab_size"]),
            rotary_pct=float(d.get("partial_rotary_factor", 1.0)),
            rope_theta=float(d["rope_theta"]), norm=norm,
            norm_eps=float(eps),
            qkv_bias=bool(d.get("use_qkv_bias", d.get("qkv_bias", False))),
            tie_embeddings=bool(d["tie_word_embeddings"]),
            overrides=tuple((k, v["value"])
                            for k, v in d.get("overrides", {}).items()))

    @property
    def rotary_dim(self) -> int:
        rd = int(self.head_dim * self.rotary_pct)
        return rd - rd % 2

    def program_config(self):
        """The program's ModelConfig for this file, checked against it."""
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config(self.registry),
                                  **dict(self.overrides))
        want = {"d_model": self.d_model, "d_ff": self.d_ff,
                "n_layers": self.n_layers, "n_heads": self.n_heads,
                "n_kv": self.n_kv, "head_dim": self.head_dim,
                "vocab": self.vocab, "rotary_pct": self.rotary_pct,
                "rope_theta": self.rope_theta,
                "norm": NORMS[self.norm], "qkv_bias": self.qkv_bias,
                "tie_embeddings": self.tie_embeddings, "act": "silu",
                "pos_emb": "rope", "kv_cache_dtype": "bf16"}
        bad = {k: (getattr(cfg, k), v) for k, v in want.items()
               if getattr(cfg, k) != v}
        if bad:
            raise ValueError(f"{self.name}: the program's configuration "
                             f"differs from the file (program, file): "
                             f"{bad}")
        if any(s.kind != "attn" or s.moe for s in cfg.pattern):
            raise ValueError(f"{self.name}: only dense attention layers "
                             f"are modelled")
        return cfg

    def matmul_params(self) -> int:
        """Parameters that every token multiplies through (the LM head
        included, the embedding lookup not)."""
        D, H, KV, hd, F = (self.d_model, self.n_heads, self.n_kv,
                           self.head_dim, self.d_ff)
        layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
        return self.n_layers * layer + self.vocab * D


load = Arch.load


def fill(path, leaf, key) -> None:
    """Dense blocks have no leaf of their own: every weight takes the
    shared rules."""
    return None


# ---------------------------------------------------------------------- #
# the reference                                                          #
# ---------------------------------------------------------------------- #
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


# ---------------------------------------------------------------------- #
# the work a token costs                                                 #
# ---------------------------------------------------------------------- #
BF16 = 2


def attn_flops(arch, n_keys: int) -> int:
    """Scores and weighted values of one query over ``n_keys`` keys, in
    every layer."""
    return 4 * arch.n_layers * arch.n_heads * arch.head_dim * n_keys


def prefill_flops(arch, n_tokens: int) -> int:
    """A prompt of ``n_tokens`` through every layer, causal attention,
    and the LM head at the last position only."""
    layers = arch.matmul_params() - arch.vocab * arch.d_model
    causal_keys = n_tokens * (n_tokens + 1) // 2
    return (2 * layers * n_tokens + attn_flops(arch, causal_keys)
            + 2 * arch.vocab * arch.d_model)


def decode_flops(arch, cached: int) -> int:
    """One new token over ``cached`` tokens already in the cache."""
    return 2 * arch.matmul_params() + attn_flops(arch, cached + 1)


def decode_attn_work(arch, lengths: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) that decode attention needs for one step: in
    every layer, per live sequence, the new query against its
    ``len + 1`` keys and values, reading those keys and values once and
    the query, writing the output.  Pad rows (length 0) are no work."""
    flops = 0
    nbytes = 0
    for n in lengths:
        if n <= 0:
            continue
        keys = n + 1
        flops += attn_flops(arch, keys)
        nbytes += arch.n_layers * BF16 * (
            2 * keys * arch.n_kv * arch.head_dim
            + 2 * arch.n_heads * arch.head_dim)
    return flops, nbytes
