"""Blocked flash attention (prefill) — Pallas TPU kernel.

TPU adaptation of the FlexGen/ZeRO compute hot spot: VMEM-tiled blocks
sized for the MXU (q/k tiles with 128-multiple dims), online softmax with
running (m, l) in VMEM scratch that persists across the innermost
(sequential) kv grid dimension.

Grid: (B * H, nq, nk) — the kv axis is innermost, so scratch accumulators
carry across kv blocks for one (head, q-block) before moving on.  Causal
blocks beyond the diagonal are skipped with pl.when (no MXU work issued).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(run if isinstance(run, bool) else run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, hd)
        k = k_ref[0].astype(jnp.float32)          # (block_k, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention_bh(q, k, v, *, causal: bool = True,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool):
    """Flat-head flash attention.

    q: (BH, Sq, hd); k, v: (BH, Sk, hd).  Returns (BH, Sq, hd).
    Sq % block_q == 0 and Sk % block_k == 0 (wrapper pads).
    """
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    nq = Sq // block_q
    nk = Sk // block_k
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """(B, Sq, H, hd) x (B, Sk, KV, hd) GQA wrapper around the kernel."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)

    def pad_to(x, blk, axis):
        S = x.shape[axis]
        t = -(-S // blk) * blk - S
        if t == 0:
            return x
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, t)
        return jnp.pad(x, pads)

    qb = pad_to(q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd),
                block_q, 1)
    kb = pad_to(kf.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd),
                block_k, 1)
    vb = pad_to(vf.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd),
                block_k, 1)
    # padded kv columns must not attend: causal masking handles q-pad rows;
    # kv pads sit at positions >= Sk which are masked when causal.  For the
    # non-causal case we mask via a huge negative bias on padded keys.
    if not causal and kb.shape[1] != Sk:
        raise ValueError("non-causal flash requires Sk % block_k == 0")
    out = flash_attention_bh(qb, kb, vb, causal=causal, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    out = out[:, :Sq].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out
