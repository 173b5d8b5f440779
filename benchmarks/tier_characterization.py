"""Paper Figs. 2-4 + Sec. III: tier latency / bandwidth characterization.

Reproduces the paper's tables from the calibrated tier models for the
three CXL systems, and MEASURES the host-RAM analogues on this machine
(device vs pinned_host vs unpinned_host transfer bandwidth/latency via
jax.device_put — the TPU-adaptation data path).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import assign_streams, paper_system
from repro.core.tiered_array import sharding_for_kind


def fig2_latency_rows():
    rows = []
    for s in "ABC":
        t = paper_system(s)
        for name in ("LDRAM", "RDRAM", "CXL"):
            rows.append((f"fig2.{s}.{name}",
                         t[name].unloaded_latency_ns,
                         f"delta_vs_ldram={t[name].unloaded_latency_ns - t['LDRAM'].unloaded_latency_ns:.0f}ns"))
    return rows


def fig3_bandwidth_rows():
    rows = []
    for s in "ABC":
        t = paper_system(s)
        for name in ("LDRAM", "RDRAM", "CXL"):
            for n in (1, 4, 8, 16, 32):
                rows.append((f"fig3.{s}.{name}.threads{n}",
                             t[name].bandwidth(n),
                             "GB/s"))
    return rows


def fig4_loaded_latency_rows():
    rows = []
    t = paper_system("C")
    for name in ("LDRAM", "RDRAM", "CXL"):
        tier = t[name]
        for frac in (0.1, 0.5, 0.9, 0.97):
            rows.append((f"fig4.C.{name}.load{int(frac*100)}",
                         tier.loaded_latency(frac * tier.peak_bw_GBps),
                         "ns"))
    return rows


def sec3_stream_assignment_rows():
    t = {k: v for k, v in paper_system("B").items() if k != "NVMe"}
    alloc, agg = assign_streams(t, 52)
    return [(f"sec3.assign.{k}", v, "streams") for k, v in alloc.items()] \
        + [("sec3.assign.aggregate", agg, "GB/s")]


def measured_host_tier_rows(n_mb: int = 64, iters: int = 5):
    """Measured device<->host-kind transfer time on this machine."""
    rows = []
    x = jnp.zeros((n_mb * 1024 * 1024 // 4,), jnp.float32)
    x = jax.device_put(x, sharding_for_kind("device"))
    jax.block_until_ready(x)
    for kind in ("pinned_host", "unpinned_host"):
        # device -> kind
        t0 = time.perf_counter()
        for _ in range(iters):
            y = jax.device_put(x, sharding_for_kind(kind))
            jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / iters
        rows.append((f"measured.dev_to_{kind}.{n_mb}MB",
                     dt * 1e6, "us"))
        rows.append((f"measured.dev_to_{kind}.bw",
                     n_mb / 1024 / dt, "GB/s"))
        # kind -> device
        t0 = time.perf_counter()
        for _ in range(iters):
            z = jax.device_put(y, sharding_for_kind("device"))
            jax.block_until_ready(z)
        dt = (time.perf_counter() - t0) / iters
        rows.append((f"measured.{kind}_to_dev.bw",
                     n_mb / 1024 / dt, "GB/s"))
    return rows


def run(registry=None):
    measured = measured_host_tier_rows()
    rows = (fig2_latency_rows() + fig3_bandwidth_rows()
            + fig4_loaded_latency_rows() + sec3_stream_assignment_rows()
            + measured)
    if registry is not None:
        # probe results double as calibration inputs: publish them
        # under probe.* so the Prometheus dump and the --json artifact
        # carry what a CostModelCalibrator would be fitted from
        registry.set_gauges({f"probe.{name}": val
                             for name, val, _ in measured
                             if isinstance(val, (int, float))})
    return rows
