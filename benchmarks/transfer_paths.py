"""Paper Figs. 5-6: accelerator <-> tier data-path bandwidth/latency.

The paper's finding: the GPU->CXL path is gated by the accelerator
interconnect (no P2P under CXL 1.1) — extra tier bandwidth doesn't help
the transfer path, and the longer path adds latency.  TPU analogue:
device<->pinned/unpinned host transfers all ride the same PCIe DMA.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import tpu_v5e_tiers
from repro.core.tiered_array import sharding_for_kind


def measured_rows():
    rows = []
    for size_mb, label in ((1, "small"), (64, "large")):
        n = size_mb * 1024 * 1024 // 4
        base = jnp.zeros((n,), jnp.float32)
        for kind in ("pinned_host", "unpinned_host"):
            x = jax.device_put(base, sharding_for_kind(kind))
            jax.block_until_ready(x)
            t0 = time.perf_counter()
            for _ in range(5):
                y = jax.device_put(x, sharding_for_kind("device"))
                jax.block_until_ready(y)
            dt = (time.perf_counter() - t0) / 5
            rows.append((f"fig5.{kind}_to_device.{label}.bw",
                         size_mb / 1024 / dt, "GB/s"))
    # Fig. 6: 64-byte latency analogue
    tiny = jnp.zeros((16,), jnp.float32)
    for kind in ("pinned_host", "unpinned_host"):
        x = jax.device_put(tiny, sharding_for_kind(kind))
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        for _ in range(200):
            y = jax.device_put(x, sharding_for_kind("device"))
            jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / 200
        rows.append((f"fig6.{kind}_to_device.64B.latency",
                     dt * 1e6, "us"))
    return rows


def model_rows():
    """The dual-hop path penalty (accelerator-host-tier) from the model."""
    t = tpu_v5e_tiers()
    direct = t["HOST"].unloaded_latency_ns
    # accelerator -> host adds the PCIe hop both ways (paper: +500ns
    # GPU-side vs +120ns CPU-side)
    dual_hop = direct + 2 * 350
    return [
        ("fig6.model.host_direct_ns", direct, "ns"),
        ("fig6.model.accel_to_host_tier_ns", dual_hop, "ns"),
        ("fig5.model.pcie_gates_bw", t["HOST"].peak_bw_GBps,
         "GB/s (interconnect bound, not tier bound)"),
    ]


def run():
    return measured_rows() + model_rows()
