"""One run of one cell: set up, serve a measured window, check, report.

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); the configuration
names its family (``bench/families/<family>.py``, "dense" where it
names none), which reads it and brings the architecture's weight rules,
float32 reference and work counts; the cell's serving settings and
check limits are ``bench/cells/<cell>.json``; each metric is read by
``bench/metrics/<metric>.py``.  Adding a configuration, a family, a
mix, a cell or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import loadgen
from bench.lookup import (BENCH, BenchError, family_of, load_arch,
                          load_reader)

ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


# ---------------------------------------------------------------------- #
# discovery                                                              #
# ---------------------------------------------------------------------- #
def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones: those that list the cell, or list no cells."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in pool if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    arch: Any               # made by its family's load()
    mix: loadgen.Mix
    serving: Dict
    check: Dict

    @classmethod
    def load(cls, bench: Dict, name: str, root: Path = BENCH) -> "Cell":
        w = find_cell(bench, name)
        settings = json.loads((root / "cells" / f"{name}.json").read_text())
        return cls(name=name, chips=int(w["chips"]),
                   arch=load_arch(root / "configs" / f"{w['config']}.json",
                                  root),
                   mix=loadgen.Mix.load(root / "traffic"
                                        / f"{w['traffic']}.json"),
                   serving=settings["serving"], check=settings["check"])


# ---------------------------------------------------------------------- #
# the run                                                                #
# ---------------------------------------------------------------------- #
class HostMeter:
    """What the host did in the window: the collector's passes and the
    process's CPU time (user and system), beside the wall time."""

    def __init__(self):
        self.passes, self.gc_s, self._t = 0, 0.0, None

    def _gc(self, phase, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.passes += 1
            self.gc_s += time.perf_counter() - self._t
            self._t = None

    def __enter__(self) -> "HostMeter":
        self._cpu = os.times()
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._gc)
        c = os.times()
        self.cpu_s = (c.user - self._cpu.user) + (c.system - self._cpu.system)


def iteration_line(starts: List[float], meter: HostMeter) -> str:
    it = np.diff(starts) * 1e3
    times = (f"median {np.median(it):.1f} ms, 90th percentile "
             f"{np.percentile(it, 90):.1f} ms" if len(it) else "none timed")
    return (f"window: {len(starts)} iterations ({times}); collector "
            f"{meter.passes} passes, {meter.gc_s:.3f} s; process cpu "
            f"{meter.cpu_s:.3f} s")


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    arch: Any
    mix: loadgen.Mix
    peak: Dict
    setup_s: float
    t_open: float
    t_close: float
    records: list
    prefills: list
    steps: list
    traced_steps: tuple
    host_kv_bytes: int
    migrated_bytes: int
    trace: object = None
    trace_summary: object = None


def device_peak(kind: str, root: Path = BENCH) -> Dict:
    table = json.loads((root / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][kind]


def check_devices(chips: int, allow_cpu: bool):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise BenchError(f"needs a TPU; JAX found {platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices


def serving_config(cell: Cell):
    from repro.serving import ServingConfig
    s = cell.serving
    if s["max_batch"] != cell.mix.clients:
        raise BenchError(f"{cell.name}: max_batch {s['max_batch']} is not "
                         f"the mix's {cell.mix.clients} clients")
    top = cell.mix.prompt_lengths[-1] + cell.mix.max_output
    if top > s["max_context"]:
        raise BenchError(f"{cell.name}: the mix's longest request "
                         f"({top} tokens) exceeds max_context")
    return ServingConfig(**s)


def warm_up(engine, cell: Cell, seed: int) -> None:
    """Compile every shape the window uses: one prefill per prompt
    length of the mix, the decode step, and the pool's eager ops on
    fresh and on written tail blocks (three tokens each)."""
    for p in loadgen.warmup_prompts(cell.mix, seed, cell.arch.vocab):
        engine.submit(p, max_new_tokens=3)
    engine.run()


def pick_checked(records, max_requests: int, seed: int) -> list:
    """The served requests to compare: the one with most tokens, and a
    sample drawn from the seed of the others."""
    served = sorted((r for r in records.values() if r.tokens),
                    key=lambda r: (-len(r.tokens), r.rid))
    if len(served) <= max_requests:
        return served
    rng = np.random.default_rng([seed, 7])
    rest = rng.choice(len(served) - 1, max_requests - 1, replace=False)
    return [served[0]] + [served[1 + i] for i in sorted(rest)]


def reference_gaps(arch, params, mix: loadgen.Mix, checked,
                   mode: Optional[str] = None) -> List[np.ndarray]:
    """Per checked request, the family's reference's gap at each served
    token: how far the served token's logit lies below the reference's
    best.  With ``mode``, the gap of the token that that lower-precision
    computation puts first instead."""
    ref = family_of(arch)
    out = []
    for r in checked:
        L, n = len(r.prompt), len(r.tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                   np.int32)])
        pos = np.arange(L - 1, L - 1 + n)
        pad = -(-(L + mix.max_output) // ref.Q_BLOCK) * ref.Q_BLOCK
        want = ref.logits(arch, params, seq, pos, pad, mix.max_output)
        tok = np.asarray(r.tokens)
        if mode is not None:
            tok = np.argmax(ref.logits(arch, params, seq, pos, pad,
                                       mix.max_output, mode=mode), -1)
        out.append(want.max(-1) - want[np.arange(n), tok])
    return out


def judge(gaps: List[np.ndarray], limit: Optional[float]):
    """(correct, widest gap, tokens compared): correct when some served
    token was compared and no gap exceeds the limit."""
    served = sum(len(g) for g in gaps)
    gap = float(max(g.max() for g in gaps)) if served else None
    return bool(served and limit is not None and gap <= limit), gap, served


def run_cell(bench: Dict, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, allow_cpu: bool = False,
             cell: Optional[Cell] = None, log=print,
             trace_dir: Path = TRACE_DIR) -> Dict:
    """Set up, measure one window, check it, and return the result
    object.  ``cell`` replaces the files' cell (tests use small ones);
    a traced run writes its profile under ``trace_dir``."""
    import jax

    from bench import tracing, weights
    from bench.probe import Probe
    from repro.serving import ServingEngine

    cell = cell or Cell.load(bench, name)
    wanted = metrics_for(bench, name, trace)
    readers = {m["name"]: load_reader(m["name"]) for m in wanted}
    devices = check_devices(cell.chips, allow_cpu)
    dev = devices[0]
    peak = (device_peak(dev.device_kind) if dev.platform == "tpu"
            else {"bf16_flops": 1.0, "hbm_bytes_s": 1.0})
    seed %= 1 << 64
    cfg = cell.arch.program_config()
    t0 = time.perf_counter()
    params = weights.make_params(cfg, seed, dev, family_of(cell.arch).fill)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    engine = ServingEngine(cfg, params, serving_config(cell))
    warm_up(engine, cell, seed)
    t2 = time.perf_counter()
    loop = loadgen.ClosedLoop(cell.mix, seed, cell.arch.vocab)
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    probe = Probe(engine, loop, seconds,
                  trace_dir=trace_dir if trace else None)
    jax.monitoring.register_event_duration_secs_listener(probe.on_compile)
    # set-up's objects (weights, engine, pool) are never scanned by the
    # collector again: its passes in the window see only new objects
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s, of which weights {t1 - t0:.3f} s and "
        f"engine with warm-up {t2 - t1:.3f} s")
    with HostMeter() as meter:
        probe.run()
    gc.unfreeze()
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"compiles in window: {probe.compiles}")
    log(iteration_line(probe.iter_starts, meter))
    log(f"preemptions in window: {probe.preemptions}")
    log(f"requests refused: {probe.refused}")
    done = sum(1 for r in probe.records.values() if r.t_finish is not None)
    log(f"requests sent: {len(probe.records)}, finished: {done}, "
        f"in flight at close: {len(probe.records) - done}")
    probe.detach()
    del engine
    gc.collect()

    t_trace, summary = None, None
    if trace:
        t_trace = tracing.load(trace_dir)
        if t_trace.device_ops or dev.platform == "tpu":
            summary = tracing.summarize(t_trace)
    run = Run(arch=cell.arch, mix=cell.mix, peak=peak, setup_s=setup_s,
              t_open=probe.t_open, t_close=probe.t_close,
              records=list(probe.records.values()),
              prefills=probe.prefills, steps=probe.steps,
              traced_steps=probe.traced_steps,
              host_kv_bytes=probe.host_kv_bytes,
              migrated_bytes=probe.migrated_bytes, trace=t_trace,
              trace_summary=summary)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checked = pick_checked(probe.records, cell.check["max_requests"], seed)
    t0 = time.perf_counter()
    gaps = reference_gaps(cell.arch, params, cell.mix, checked)
    limit = cell.check["limits"]["max_logit_gap"]
    correct, gap, served = judge(gaps, limit)
    log(f"checked {len(checked)} requests, {served} served tokens "
        f"against the float32 reference in {time.perf_counter() - t0:.3f} s")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(probe.records),
           "failed": probe.refused, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9
        out["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in summary.top_ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in summary.top_gaps]}
    out["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_benchmark()
        out = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    c = out["checks"]["max_logit_gap"]
    print(json.dumps(out), flush=True)
    print(f"max_logit_gap {c['value']!r} limit {c['limit']!r}",
          file=sys.stderr, flush=True)
    return 0
