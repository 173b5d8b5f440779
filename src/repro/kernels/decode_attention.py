"""GQA decode attention — Pallas TPU kernel (the FlexGen Sec. IV-B hot spot).

The paper runs decode attention on the CPU next to the offloaded KV cache
("computation offloaded to the CPU benefits from the extra CXL
bandwidth").  On TPU the analogous structure is a bandwidth-bound kernel
streaming the (possibly tier-resident) KV cache through VMEM in blocks:
one query row per sequence, online softmax across kv blocks, grouped
heads so each KV head is read ONCE for its `rep` query heads (a GQA
bandwidth optimization a naive repeat would forfeit).

Grid: (B, nk) — kv blocks innermost and sequential, accumulators live in
VMEM scratch.  kv_len masks the unwritten tail of the cache buffer; it
rides the scalar-prefetch channel into SMEM, because Mosaic refuses a
rank-1 VMEM block narrower than 128 lanes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the largest power-of-two kv block that fits v5e's scoped VMEM at GQA
# widths (KV=4, hd=128): the (block_k, KV, hd) tiles pad KV up to a full
# sublane tile, and block_k=256 runs out of VMEM there
DEF_BLOCK_K = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_k: int, rep: int,
                   scale: float):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[pl.program_id(0)]
    q = q_ref[0].astype(jnp.float32)             # (H, hd)  H = KV*rep
    k = k_ref[0].astype(jnp.float32)             # (block_k, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    KV = k.shape[1]
    hd = q.shape[-1]
    # grouped scores: q (KV, rep, hd) x k (block_k, KV, hd) -> (KV,rep,bk)
    qg = q.reshape(KV, rep, hd)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,)))) * scale   # (KV, rep, block_k)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (KV, rep, k.shape[0]), 2)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]                           # (KV, rep)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    # p (KV, rep, bk) x v (bk, KV, hd) -> (KV, rep, hd)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))))
    acc_scr[...] = acc_scr[...] * corr[..., None] + pv
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(KV * rep, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_len, *,
                     block_k: int = DEF_BLOCK_K, interpret: bool):
    """q: (B, H, hd); caches: (B, S, KV, hd); kv_len: (B,) or scalar.

    Returns (B, H, hd).  S % block_k == 0 (cache buffers are padded)."""
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    assert S % block_k == 0, f"cache len {S} % block {block_k}"
    nk = S // block_k
    scale = 1.0 / math.sqrt(hd)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    kernel = functools.partial(_decode_kernel, block_k=block_k, rep=rep,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_k, KV, hd),
                         lambda b, j, lens: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, KV, hd),
                         lambda b, j, lens: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, rep), jnp.float32),
            pltpu.VMEM((KV, rep), jnp.float32),
            pltpu.VMEM((KV, rep, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(kv_len, q, k_cache, v_cache)
