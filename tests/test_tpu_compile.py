"""The main path's Pallas kernels compile for a TPU v5e (Mosaic).

Interpret mode, which every other kernel test uses, accepts block
shapes and VMEM footprints that the chip's compiler refuses.  These
tests ahead-of-time compile each kernel for a *described* ``v5e:2x2``
topology — no chip attached — at published model widths, and assert
that the compiled program really holds the Mosaic kernel.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every xdist
worker imports this file.  The tests share one ``xdist_group``, so they
run in one worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import fused_adam as adam
from repro.kernels import tiered_gather as tg

# (H, KV, hd): stablelm-1.6b (MHA) and qwen3-moe-30b-a3b (GQA)
WIDTHS = {"stablelm-1.6b": (32, 32, 64), "qwen3-moe-30b-a3b": (32, 4, 128)}
B = 8
S = 1024            # the engine's padded context at max_context=1024
BLOCK_TOKENS = 16   # the engine's paged block (decode block_k == bt)

pytestmark = pytest.mark.xdist_group("libtpu")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_k", [BLOCK_TOKENS, dec.DEF_BLOCK_K])
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_decode_attention_compiles(one_chip, model, block_k):
    H, KV, hd = WIDTHS[model]
    _assert_mosaic(
        lambda q, k, v, n: dec.decode_attention(q, k, v, n, block_k=block_k,
                                                interpret=False),
        _spec(one_chip, (B, H, hd)), _spec(one_chip, (B, S, KV, hd)),
        _spec(one_chip, (B, S, KV, hd)), _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_paged_decode_attention_compiles(one_chip, model):
    H, KV, hd = WIDTHS[model]
    nb = S // BLOCK_TOKENS
    pool = (B * nb, BLOCK_TOKENS, KV, hd)
    _assert_mosaic(
        lambda q, k, v, t, n, kn, vn: tg.paged_decode_attention(
            q, k, v, t, n, kn, vn, block_tokens=BLOCK_TOKENS,
            interpret=False),
        _spec(one_chip, (B, H, hd)), _spec(one_chip, pool),
        _spec(one_chip, pool), _spec(one_chip, (B, nb), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), _spec(one_chip, (B, KV, hd)),
        _spec(one_chip, (B, KV, hd)))


def test_fused_expert_ffn_compiles(one_chip):
    D, F, E, K = 2048, 768, 128, 8          # qwen3-moe-30b-a3b experts
    _assert_mosaic(
        lambda x, g, u, d, i, w: tg.fused_expert_ffn(x, g, u, d, i, w,
                                                     interpret=False),
        _spec(one_chip, (B, D)), _spec(one_chip, (E, D, F)),
        _spec(one_chip, (E, D, F)), _spec(one_chip, (E, F, D)),
        _spec(one_chip, (B, K), jnp.int32),
        _spec(one_chip, (B, K), jnp.float32))


def test_fused_adam_compiles(one_chip):
    shape = (2048, 5632)                    # a stablelm-1.6b MLP matrix
    f32 = [_spec(one_chip, shape, jnp.float32) for _ in range(4)]
    _assert_mosaic(
        lambda p, m, v, g: adam.fused_adam(
            p, m, v, g, lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
            b1c=0.1, b2c=0.05, interpret=False),
        *f32)
