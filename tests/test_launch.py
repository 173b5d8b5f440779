"""Launch layer: cell building, jaxpr cost walker, HLO collective parse."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H
from repro.launch import jaxpr_cost as JC
from repro.launch.mesh import dp_axes, dp_size, make_mesh, tp_size


def test_mesh_helpers():
    m = make_mesh((1, 1), ("data", "model"))
    assert dp_axes(m) == ("data",)
    assert dp_size(m) == 1 and tp_size(m) == 1


def test_jaxpr_cost_dot():
    def f(a, b):
        return a @ b
    a = jnp.zeros((64, 128))
    b = jnp.zeros((128, 32))
    c = JC.step_cost(f, a, b)
    assert c["flops"] == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_jaxpr_cost_scan_multiplies_trips():
    def f(xs, w):
        def body(c, x):
            return c + (x @ w).sum(), None
        out, _ = jax.lax.scan(body, 0.0, xs)
        return out
    xs = jnp.zeros((7, 16, 32))
    w = jnp.zeros((32, 8))
    c = JC.step_cost(f, xs, w)
    per_trip = 2 * 16 * 32 * 8
    assert c["flops"] >= 7 * per_trip
    assert c["flops"] < 7 * per_trip * 1.5


def test_jaxpr_cost_grad_counts_backward():
    def loss(w, x):
        return jnp.sum((x @ w) ** 2)
    w = jnp.zeros((32, 16))
    x = jnp.zeros((8, 32))
    fwd = JC.step_cost(loss, w, x)["flops"]
    both = JC.step_cost(jax.grad(loss), w, x)["flops"]
    assert both > 1.8 * fwd  # bwd ≈ 2x fwd for a matmul


def test_collective_parser_trip_counts():
    hlo = """
HloModule m

%body (p: (s32[], f32[])) -> (s32[], f32[]) {
  %ar = f32[1024]{0} all-reduce(%x), replica_groups=[4,8]<=[32], to_apply=%add
}

ENTRY %main () -> f32[] {
  %w = (s32[], f32[]) while(%t), condition=%c, body=%body, backend_config={"known_trip_count":{"n":"10"}}
}
"""
    stats = H.collective_bytes(hlo, 32)
    # 1024 f32 = 4096 bytes; all-reduce wire = 2*(7/8)*4096; x10 trips
    want = 2 * (7 / 8) * 4096 * 10
    assert stats.wire_bytes == pytest.approx(want, rel=0.01)
    assert stats.counts["all-reduce"] == 1


def test_collective_parser_plain():
    hlo = """
ENTRY %main () -> f32[] {
  %ag = bf16[256,128]{1,0} all-gather(%x), replica_groups=[16,16]<=[256]
}
"""
    stats = H.collective_bytes(hlo, 256)
    want = 256 * 128 * 2 * (15 / 16)
    assert stats.wire_bytes == pytest.approx(want, rel=0.01)


def test_build_cell_tiny_mesh_lowers():
    """A full train cell lowers+compiles on a 1x1 mesh (wiring check)."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.launch.specs import build_cell, SHAPES
    cfg = get_smoke_config("stablelm-1.6b")
    cfg = dataclasses.replace(cfg, vocab=128)
    mesh = make_mesh((1, 1), ("data", "model"))
    # shrink the shape for CPU compile
    import repro.configs.base as B
    shape = B.ShapeConfig("train_4k", 64, 2, "train")
    import repro.launch.specs as SP
    old = SP.SHAPES
    SP.SHAPES = dict(old, train_4k=shape)
    try:
        cell = build_cell("stablelm-1.6b", "train_4k", mesh,
                          cfg_override=cfg)
        with mesh:
            compiled = cell.jit().lower(*cell.args).compile()
        assert compiled.cost_analysis() is not None
    finally:
        SP.SHAPES = old


def test_roofline_terms_math():
    stats = H.CollectiveStats(wire_bytes=50e9)
    r = H.roofline_terms(197e12 * 256, 819e9 * 256, stats, 256, 1e15)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(1.0)
    assert r.dominant in ("compute", "memory", "collective")


def test_vmem_fused_accounting_reduces_softmax_traffic():
    """Block-sized attention intermediates stop hitting HBM under the
    VMEM-residency model (the Pallas-kernel fusion, §Perf O7)."""
    import jax.numpy as jnp

    def attn(q, k, v):
        s = jnp.einsum("qd,kd->qk", q, k)
        p = jax.nn.softmax(s, axis=-1)
        return p @ v

    q = jnp.zeros((128, 64))
    k = jnp.zeros((128, 64))
    v = jnp.zeros((128, 64))
    base = JC.step_cost(attn, q, k, v)
    fused = JC.step_cost(attn, q, k, v, vmem_bytes=64 * 1024**2,
                         n_chips=1)
    assert fused["bytes"] < base["bytes"]
    # q/k/v always charged (persistent inputs)
    assert fused["bytes"] >= 3 * 128 * 64 * 4


def test_cast_absorbs_read_at_source_width():
    import jax.numpy as jnp

    def deq(c):
        return (c.astype(jnp.float32) * 2.0).sum()

    c8 = jnp.zeros((1024, 128), jnp.int8)
    cost = JC.step_cost(deq, c8)
    # charged at int8 width (+ small reduce output), not fp32
    assert cost["bytes"] < 1024 * 128 * 2


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    from repro.launch import compile_cache as cc
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = cc.place_compile_cache()
        assert path == str(cc.REPO_CACHE) == jax.config.jax_compilation_cache_dir
        assert cc.REPO_CACHE.parent.joinpath("pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_dir_is_the_only_one(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there, and JAX's
    one cache directory is still that one after a compile."""
    import os
    import subprocess
    import sys

    from repro.launch import compile_cache as cc
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(cc.REPO_CACHE.parent / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import place_compile_cache\n"
            "print(place_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path)] * 2
    assert os.listdir(tmp_path), "no cache entry was written"
