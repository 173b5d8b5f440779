"""Fused tiered-gather kernels: compute directly over tier-resident
block layouts (the PR 9 hot path).

The paper's Sec. IV-B offloaded-inference study is bandwidth-bound on
the tier link, and "Demystifying CXL Memory" quantifies the cliff a
gather-then-compute path pays twice: staging tier-resident blocks into
a contiguous buffer reads every byte once to copy it and once more to
compute on it (plus the staging write).  These kernels instead index
the *pool* layout directly through a scalar-prefetched block table, so
each tier-resident byte crosses the link exactly once, into VMEM,
already in compute order.

Two kernels:

``paged_decode_attention``
    GQA decode attention over the paged KV pool: the per-layer pool
    stores ``(num_blocks, block_tokens, KV, hd)`` and a per-sequence
    block table names which pool blocks hold the sequence's tokens.
    The grid walks ``(batch, table slot)``; the block table rides the
    scalar-prefetch channel so each slot's ``index_map`` resolves to
    the *physical* pool block — no contiguous staging copy exists.
    The new token's (k, v) — computed this step, not yet in the pool —
    folds into the online softmax at finalize, replacing the unfused
    path's cache scatter.

``fused_expert_ffn``
    Top-k MoE expert FFN over the stacked expert store
    ``(n_experts, d_model, d_ff)``: the routed expert ids ride the
    scalar-prefetch channel, so each (token, slot) grid step streams
    exactly its expert's weights from their resident tier into VMEM.
    The gather-then-compute baseline (``ref.expert_ffn``) materializes
    the ``(B, k, d_model, d_ff)`` selection first — top_k/n_experts of
    the store copied per token *before* any FLOP.

Both run under ``interpret=True`` on the CPU (the tests), like every
kernel in this package, and compile to Mosaic on the TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# F columns of an expert streamed per grid step: whole (D, F) expert
# blocks at published MoE widths overflow v5e's scoped VMEM once
# double-buffered
BLOCK_F = 256


# ---------------------------------------------------------------------- #
# paged decode attention                                                  #
# ---------------------------------------------------------------------- #
def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref,
                         knew_ref, vnew_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, block_tokens: int,
                         rep: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]
    q = q_ref[0].astype(jnp.float32)              # (H, hd)  H = KV*rep
    k = k_ref[0].astype(jnp.float32)              # (bt, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    KV = k.shape[1]
    hd = q.shape[-1]
    qg = q.reshape(KV, rep, hd)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,)))) * scale   # (KV, rep, bt)
    # logical position of each pool-block slot: table order, not
    # physical block id — padded table entries land beyond kv_len
    k_pos = j * block_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (KV, rep, k.shape[0]), 2)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]                           # (KV, rep)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))))       # (KV, rep, hd)
    acc_scr[...] = acc_scr[...] * corr[..., None] + pv
    m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        # fold the step's new token (position kv_len, computed in-layer
        # so never in the pool) into the online softmax — the fused
        # replacement for the unfused path's cache scatter
        kn = knew_ref[0].astype(jnp.float32)      # (KV, hd)
        vn = vnew_ref[0].astype(jnp.float32)
        sn = (qg * kn[:, None, :]).sum(-1) * scale      # (KV, rep)
        m_fin = jnp.maximum(m_scr[...], sn)
        pn = jnp.exp(sn - m_fin)
        corr_f = jnp.exp(m_scr[...] - m_fin)
        l_fin = l_scr[...] * corr_f + pn
        acc = acc_scr[...] * corr_f[..., None] + pn[..., None] \
            * vn[:, None, :]
        out = acc / jnp.maximum(l_fin, 1e-30)[..., None]
        o_ref[0] = out.reshape(KV * rep, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_tokens", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tbl, kv_len,
                           k_new, v_new, *, block_tokens: int,
                           interpret: bool):
    """Decode attention straight over the paged pool layout.

    q: (B, H, hd); k_pool/v_pool: (num_blocks, block_tokens, KV, hd) —
    the tier-resident per-layer pool stores; block_tbl: (B, nb) int32
    physical block ids in logical order (pad slots may repeat id 0 —
    they are masked by ``kv_len``); kv_len: (B,) tokens already cached;
    k_new/v_new: (B, KV, hd) — this step's token, attended at position
    ``kv_len`` without ever being staged.  Returns (B, H, hd) attention
    over ``kv_len + 1`` positions.  The block table and ``kv_len`` both
    ride the scalar-prefetch channel into SMEM.
    """
    B, H, hd = q.shape
    KV = k_pool.shape[2]
    rep = H // KV
    nb = block_tbl.shape[1]
    assert k_pool.shape[1] == block_tokens, \
        f"pool block_tokens {k_pool.shape[1]} != {block_tokens}"
    scale = 1.0 / math.sqrt(hd)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    block_tbl = block_tbl.astype(jnp.int32)
    kernel = functools.partial(_paged_decode_kernel,
                               block_tokens=block_tokens, rep=rep,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, tbl, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_tokens, KV, hd),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, block_tokens, KV, hd),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, j, tbl, lens: (b, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, j, tbl, lens: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd),
                               lambda b, j, tbl, lens: (b, 0, 0)),
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
    )(block_tbl, kv_len, q, k_pool, v_pool, k_new, v_new)


# ---------------------------------------------------------------------- #
# fused expert FFN                                                        #
# ---------------------------------------------------------------------- #
def _expert_ffn_kernel(ids_ref, wts_ref, x_ref, wg_ref, wu_ref, wd_ref,
                       o_ref, acc_scr):
    b = pl.program_id(0)
    k = pl.program_id(1)
    f = pl.program_id(2)
    last = (k == pl.num_programs(1) - 1) & (f == pl.num_programs(2) - 1)

    @pl.when((k == 0) & (f == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the FFN is separable over F: silu(x @ wg[:, f]) * (x @ wu[:, f])
    # only ever meets wd[f, :], so each F tile adds its share of the
    # down projection to the accumulator
    x = x_ref[0]                                  # (1, D)
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)  # (1, tf)
    down = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] + wts_ref[b, k] * down

    @pl.when(last)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_expert_ffn(x, w_gate, w_up, w_down, expert_ids, expert_wts,
                     *, interpret: bool):
    """Top-k expert FFN gathered straight from the stacked expert store.

    x: (B, D); w_gate/w_up: (E, D, F); w_down: (E, F, D) — the
    tier-resident expert weight blocks; expert_ids: (B, K) int32 routed
    experts per token; expert_wts: (B, K) normalized router weights.
    Returns (B, D): sum_k w[b,k] * ffn_silu(x[b]; expert ids[b,k]).
    Only the K routed experts' weights are read per token,
    ``min(F, BLOCK_F)`` columns of F at a time; that tile must divide F.
    """
    B, D = x.shape
    E, _, F = w_gate.shape
    K = expert_ids.shape[1]
    tf = min(F, BLOCK_F)
    if F % tf:
        raise ValueError(f"d_ff {F} is not a multiple of BLOCK_F {BLOCK_F}")
    expert_ids = expert_ids.astype(jnp.int32)
    expert_wts = expert_wts.astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, F // tf),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, k, f, ids, wts: (b, 0, 0)),
            pl.BlockSpec((1, D, tf),
                         lambda b, k, f, ids, wts: (ids[b, k], 0, f)),
            pl.BlockSpec((1, D, tf),
                         lambda b, k, f, ids, wts: (ids[b, k], 0, f)),
            pl.BlockSpec((1, tf, D),
                         lambda b, k, f, ids, wts: (ids[b, k], f, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D),
                               lambda b, k, f, ids, wts: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32)],
    )
    out = pl.pallas_call(
        _expert_ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, D), x.dtype),
        interpret=interpret,
    )(expert_ids, expert_wts, x[:, None], w_gate, w_up, w_down)
    return out[:, 0]
