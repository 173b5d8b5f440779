"""Fused tiered-gather kernels vs gather-then-compute oracles.

The fused paged-decode kernel reads KV blocks straight out of the
tier-resident pool layout through a scalar-prefetched block-index
table; the oracle stages the same blocks into a contiguous cache first
(the copy the kernel eliminates).  Agreement across block tables,
ragged kv_len, and routing patterns is what lets the engine swap the
staged path for the fused one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref


def _paged_inputs(seed, B, H, KV, hd, bt, nb, num_blocks, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = (jax.random.normal(ks[0], (B, H, hd)) * 0.3).astype(dtype)
    k_pool = (jax.random.normal(ks[1], (num_blocks, bt, KV, hd))
              * 0.3).astype(dtype)
    v_pool = (jax.random.normal(ks[2], (num_blocks, bt, KV, hd))
              * 0.3).astype(dtype)
    tbl = jax.random.randint(ks[3], (B, nb), 0, num_blocks, jnp.int32)
    k_new = (jax.random.normal(ks[4], (B, KV, hd)) * 0.3).astype(dtype)
    v_new = (jax.random.normal(ks[5], (B, KV, hd)) * 0.3).astype(dtype)
    return q, k_pool, v_pool, tbl, k_new, v_new


# ---------------------- fused paged decode ---------------------------- #
@pytest.mark.parametrize("B,H,KV,hd,bt,nb,num_blocks", [
    (1, 4, 4, 64, 16, 2, 8),       # MHA, tiny pool
    (4, 8, 2, 64, 32, 4, 16),      # GQA
    (2, 16, 1, 32, 64, 3, 32),     # MQA, odd block count
])
def test_paged_decode_attention_sweep(B, H, KV, hd, bt, nb, num_blocks):
    q, kp, vp, tbl, kn, vn = _paged_inputs(0, B, H, KV, hd, bt, nb,
                                           num_blocks)
    # ragged: every row caches a different prefix of its blocks
    kv_len = jnp.asarray([(i * 7 + 3) % (nb * bt) for i in range(B)],
                         jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, kv_len, kn, vn,
                                     block_tokens=bt)
    want = ref.paged_decode_attention(q, kp, vp, tbl, kv_len, kn, vn)
    assert got.shape == (B, H, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv_len", [0, 31, 32, 33, 127])
def test_paged_decode_attention_block_boundaries(kv_len):
    """The new token lands exactly at/around block edges (and at 0:
    attention over nothing but the freshly scattered token)."""
    B, H, KV, hd, bt, nb = 2, 4, 2, 32, 32, 4
    q, kp, vp, tbl, kn, vn = _paged_inputs(1, B, H, KV, hd, bt, nb, 8)
    lens = jnp.full((B,), kv_len, jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, lens, kn, vn,
                                     block_tokens=bt)
    want = ref.paged_decode_attention(q, kp, vp, tbl, lens, kn, vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_paged_decode_attention_shared_blocks_and_bf16():
    """Different sequences' tables may point at the same physical
    blocks (the pool reuses ids); bf16 pools stay within bf16 slack."""
    B, H, KV, hd, bt, nb = 3, 8, 2, 64, 16, 3
    q, kp, vp, _, kn, vn = _paged_inputs(2, B, H, KV, hd, bt, nb, 4,
                                         dtype=jnp.bfloat16)
    tbl = jnp.asarray([[0, 1, 2], [2, 1, 0], [1, 1, 3]], jnp.int32)
    lens = jnp.asarray([40, 17, 5], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, lens, kn, vn,
                                     block_tokens=bt)
    want = ref.paged_decode_attention(q, kp, vp, tbl, lens, kn, vn)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


@settings(max_examples=8, deadline=None)
@given(nb=st.integers(1, 4), kv=st.sampled_from([1, 2]),
       rep=st.sampled_from([1, 4]), seed=st.integers(0, 10))
def test_paged_decode_attention_property(nb, kv, rep, seed):
    B, hd, bt = 2, 32, 16
    q, kp, vp, tbl, kn, vn = _paged_inputs(seed, B, kv * rep, kv, hd,
                                           bt, nb, 8)
    kv_len = jnp.asarray([seed % (nb * bt), (seed * 3 + 1) % (nb * bt)],
                         jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, kv_len, kn, vn,
                                     block_tokens=bt)
    want = ref.paged_decode_attention(q, kp, vp, tbl, kv_len, kn, vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ------------------------ fused expert FFN ---------------------------- #
def _expert_inputs(seed, E, D, F, B, K, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = (jax.random.normal(ks[0], (B, D)) * 0.3).astype(dtype)
    wg = (jax.random.normal(ks[1], (E, D, F)) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, D, F)) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, F, D)) * 0.1).astype(dtype)
    ids = jax.random.randint(ks[4], (B, K), 0, E, jnp.int32)
    wts = jax.nn.softmax(jax.random.normal(ks[5], (B, K)), axis=-1)
    return x, wg, wu, wd, ids, wts.astype(dtype)


@pytest.mark.parametrize("E,D,F,B,K,dtype,tol", [
    (4, 16, 32, 1, 1, jnp.float32, 2e-3),
    (8, 64, 128, 6, 2, jnp.float32, 2e-3),
    (16, 32, 64, 5, 4, jnp.float32, 2e-3),
    # F spans several BLOCK_F tiles, as at published MoE widths
    (4, 64, 512, 3, 2, jnp.float32, 2e-3),
    (8, 32, 768, 2, 3, jnp.float32, 2e-3),
    (8, 64, 768, 3, 2, jnp.bfloat16, 3e-2),
])
def test_fused_expert_ffn_sweep(E, D, F, B, K, dtype, tol):
    x, wg, wu, wd, ids, wts = _expert_inputs(0, E, D, F, B, K, dtype)
    got = ops.fused_expert_ffn(x, wg, wu, wd, ids, wts)
    want = ref.expert_ffn(x, wg, wu, wd, ids, wts)
    assert got.shape == (B, D) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fused_expert_ffn_rejects_ragged_f_tiles():
    x, wg, wu, wd, ids, wts = _expert_inputs(0, 2, 16, 384, 1, 1)
    with pytest.raises(ValueError, match="BLOCK_F"):
        ops.fused_expert_ffn(x, wg, wu, wd, ids, wts)


def test_fused_expert_ffn_duplicate_experts():
    """A token routed twice to the same expert accumulates both
    weighted contributions (top-k ties are legal routing output)."""
    E, D, F, B = 4, 32, 64, 3
    x, wg, wu, wd, _, _ = _expert_inputs(1, E, D, F, B, 2)
    ids = jnp.asarray([[2, 2], [0, 3], [1, 1]], jnp.int32)
    wts = jnp.asarray([[0.7, 0.3], [0.5, 0.5], [1.0, 0.0]], jnp.float32)
    got = ops.fused_expert_ffn(x, wg, wu, wd, ids, wts)
    want = ref.expert_ffn(x, wg, wu, wd, ids, wts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_fused_expert_ffn_matches_model_moe_dense_equivalent():
    """With every expert identical, the routed sum collapses to the
    plain FFN regardless of routing — a closed-form cross-check that
    needs no staging oracle at all."""
    E, D, F, B, K = 4, 32, 64, 5, 2
    x, wg, wu, wd, ids, wts = _expert_inputs(2, E, D, F, B, K)
    wg = jnp.broadcast_to(wg[:1], wg.shape)
    wu = jnp.broadcast_to(wu[:1], wu.shape)
    wd = jnp.broadcast_to(wd[:1], wd.shape)
    got = ops.fused_expert_ffn(x, wg, wu, wd, ids, wts)
    xf = x.astype(jnp.float32)
    h = jax.nn.silu(xf @ wg[0].astype(jnp.float32)) \
        * (xf @ wu[0].astype(jnp.float32))
    want = (h @ wd[0].astype(jnp.float32)) \
        * wts.sum(-1, keepdims=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
