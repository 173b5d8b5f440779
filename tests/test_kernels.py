"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref


# --------------------------- fused adam ------------------------------- #
@pytest.mark.parametrize("n", [1, 127, 128, 1000, 4096, 70000])
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_fused_adam_sweep(n, gdtype):
    k = jax.random.PRNGKey(n)
    master = jax.random.normal(k, (n,), jnp.float32)
    m = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 0.1
    v = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (n,))) * 0.01
    g = jax.random.normal(jax.random.PRNGKey(3), (n,)).astype(gdtype)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
              b1c=0.1, b2c=0.05)
    got = ops.fused_adam(master, m, v, g, **kw)
    want = ref.fused_adam(master, m, v, g, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(3, 5), (16, 128), (2, 3, 4, 5)])
def test_fused_adam_nd_shapes(shape):
    k = jax.random.PRNGKey(0)
    master = jax.random.normal(k, shape, jnp.float32)
    m = jnp.zeros(shape)
    v = jnp.zeros(shape)
    g = jax.random.normal(jax.random.PRNGKey(1), shape)
    kw = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, wd=0.0,
              b1c=0.1, b2c=0.001)
    got = ops.fused_adam(master, m, v, g, **kw)
    want = ref.fused_adam(master, m, v, g, **kw)
    assert got[0].shape == shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ------------------------- flash attention ---------------------------- #
@pytest.mark.parametrize("B,Sq,Sk,H,KV,hd", [
    (1, 128, 128, 4, 4, 64),      # MHA square
    (2, 256, 256, 8, 2, 64),      # GQA
    (1, 384, 128, 4, 1, 32),      # MQA, Sq > Sk
    (2, 130, 259, 4, 4, 64),      # ragged (padding path)
])
def test_flash_attention_sweep(B, Sq, Sk, H, KV, hd):
    k0 = jax.random.PRNGKey(0)
    q = jax.random.normal(k0, (B, Sq, H, hd), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Sk, KV, hd)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Sk, KV, hd)) * 0.5
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q = (jax.random.normal(jax.random.PRNGKey(0), (1, 128, 4, 64))
         * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.PRNGKey(1), (1, 128, 4, 64))
         * 0.5).astype(jnp.bfloat16)
    v = (jax.random.normal(jax.random.PRNGKey(2), (1, 128, 4, 64))
         * 0.5).astype(jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_matches_model_chunked_attention():
    """Kernel vs the model's pure-JAX chunked attention (same algorithm)."""
    from repro.models.modules import chunked_attention
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 8, 64)) * 0.3
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 4, 64)) * 0.3
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 4, 64)) * 0.3
    a = ops.flash_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, chunk_q=64, chunk_kv=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)


# ------------------------- decode attention --------------------------- #
@pytest.mark.parametrize("B,S,H,KV,hd,blk", [
    (1, 256, 4, 4, 64, 128),
    (4, 512, 8, 2, 64, 256),
    (2, 1024, 16, 1, 128, 256),
])
def test_decode_attention_sweep(B, S, H, KV, hd, blk):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
    kv_len = jnp.arange(1, B + 1, dtype=jnp.int32) * (S // (B + 1)) + 1
    got = ops.decode_attention(q, kc, vc, kv_len, block_k=blk)
    want = jnp.stack([
        ref.decode_attention(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                             kv_len[i])[0]
        for i in range(B)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(s_mult=st.integers(1, 4), kv=st.sampled_from([1, 2, 4]),
       rep=st.sampled_from([1, 2, 4]))
def test_decode_attention_property(s_mult, kv, rep):
    B, hd = 2, 32
    S = 128 * s_mult
    H = kv * rep
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, kv, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, kv, hd))
    got = ops.decode_attention(q, kc, vc, S, block_k=128)
    want = ref.decode_attention(q, kc, vc, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# --------------------------- backend selection ------------------------ #
@pytest.mark.parametrize("entry", [
    "decode_attention.decode_attention", "flash_attention.flash_attention",
    "flash_attention.flash_attention_bh", "fused_adam.fused_adam",
    "fused_adam.fused_adam_2d", "tiered_gather.paged_decode_attention",
    "tiered_gather.fused_expert_ffn"])
def test_kernel_entries_require_interpret(entry):
    """A direct caller must say how the kernel runs: a default of
    interpret=True would run the interpreter on the chip in silence."""
    import importlib
    import inspect
    mod, name = entry.split(".")
    fn = getattr(importlib.import_module(f"repro.kernels.{mod}"), name)
    param = inspect.signature(fn).parameters["interpret"]
    assert param.default is inspect.Parameter.empty


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_follows_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="not on 'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is want
