"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV.  Modules:
  tier_characterization  Figs. 2-4 + Sec. III stream packing
  transfer_paths         Figs. 5-6 accelerator<->tier path
  zero_offload_train     Figs. 8-9 ZeRO-Offload policies
  flexgen_serve          Figs. 11-12 + Table II serving
  oli_hpc                Figs. 13-15 + Table III OLI
  tiering_migration      Figs. 16-17 migration x placement
  serve_scheduler_bench  continuous batching: static KV split vs tiering
  adaptive_replan_bench  telemetry-driven adaptive re-interleaving vs
                         static plans on a phase-shifting workload
  topology_bench         hop-distance costing: near vs far socket,
                         distance-weighted interleave, link contention
  multi_tenant_bench     two tenants on one pool: fair-share fast-tier
                         arbitration vs static splits and free-for-all
  calibration_bench      prediction audit + self-calibrating cost model
                         on a perturbed testbed vs the builder defaults
  noisy_neighbor_bench   interference-class QoS: blame attribution +
                         violation-predictive admission vs the flat floor
  moe_expert_bench       MoE expert tier residency: predictive expert
                         prefetch vs LRU on recurrent routing phases
  multi_host_bench       multi-host plane: headroom+distance session
                         routing vs capacity-blind baselines, namespace
                         conservation, per-replica budget caps
  kernel_bench           Pallas kernel microbenches
  roofline               per-cell roofline from the dry-run artifacts

Usage: ``python benchmarks/run.py [--list] [--smoke] [--json PATH]
[name ...]`` (no names = all).  Unknown names are an error.
``--smoke`` asks each module that supports it for a reduced, CI-sized
run.  ``--json PATH`` additionally writes a structured results
artifact — per-bench status, wall time, and every metric row — which
CI uploads on each run so the repo accumulates a machine-readable
perf trajectory.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import platform
import sys
import time
import traceback

# script invocation puts benchmarks/ on sys.path; the package imports
# (`benchmarks.<name>`) need the repo root, and the bench modules need
# `repro` importable even when PYTHONPATH=src was not exported
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
_SRC = os.path.join(_ROOT, "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.obs import MetricsRegistry  # noqa: E402

MODULES = [
    "tier_characterization",
    "transfer_paths",
    "zero_offload_train",
    "flexgen_serve",
    "oli_hpc",
    "tiering_migration",
    "serve_scheduler_bench",
    "adaptive_replan_bench",
    "topology_bench",
    "multi_tenant_bench",
    "calibration_bench",
    "noisy_neighbor_bench",
    "moe_expert_bench",
    "multi_host_bench",
    "kernel_bench",
    "roofline",
]


def write_json(path: str, results, smoke: bool, wall_s: float,
               registry: MetricsRegistry, argv=None) -> None:
    """Persist the structured results artifact (CI perf trajectory)."""
    payload = {
        "schema_version": 1,
        "smoke": smoke,
        # the exact invocation, so trajectory diffs can refuse to
        # compare runs produced under different conditions
        "argv": list(argv if argv is not None else sys.argv[1:]),
        "python": platform.python_version(),
        "benchmarks": results,
        "registry": registry.snapshot(),
        "totals": {
            "benchmarks": len(results),
            "failed": sum(1 for r in results if r["status"] == "failed"),
            "metrics": sum(len(r["metrics"]) for r in results),
            "wall_s": wall_s,
        },
    }
    import jax
    payload["jax"] = jax.__version__
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}: {payload['totals']['metrics']} metrics "
          f"from {len(results)} benchmarks", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("names", nargs="*",
                    help="benchmark modules to run (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="list available benchmark names and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced run for modules that support it")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write structured results (per-bench status, "
                         "wall time, metric rows) to PATH")
    ap.add_argument("--prom", metavar="PATH", default=None,
                    help="write the central registry (every metric row "
                         "plus module-published probe/calibration "
                         "gauges) as Prometheus text exposition to PATH")
    args = ap.parse_args(argv)

    if args.list:
        for name in MODULES:
            print(name)
        return

    unknown = [n for n in args.names if n not in MODULES]
    if unknown:
        print(f"unknown benchmark(s): {', '.join(unknown)}\n"
              f"available: {', '.join(MODULES)}", file=sys.stderr)
        sys.exit(2)

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    only = args.names or MODULES
    failures = 0
    results = []
    # every metric row also lands in a central registry so the JSON
    # artifact (and anything downstream) reads one uniform namespace
    registry = MetricsRegistry()
    t_start = time.time()
    for name in MODULES:
        if name not in only:
            continue
        t0 = time.time()
        entry = {"name": name, "status": "ok", "wall_s": 0.0,
                 "metrics": []}
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            params = inspect.signature(mod.run).parameters
            kwargs = {}
            if args.smoke and "smoke" in params:
                kwargs["smoke"] = True
            if "registry" in params:
                # modules that publish gauges directly (probe results,
                # calibration state) write into the central registry
                kwargs["registry"] = registry
            rows = mod.run(**kwargs)
            for key, val, derived in rows:
                if isinstance(val, float):
                    print(f"{key},{val:.6g},{derived}")
                else:
                    print(f"{key},{val},{derived}")
                entry["metrics"].append(
                    {"name": key, "value": val, "unit": derived})
                if isinstance(val, (int, float)) \
                        and not isinstance(val, bool):
                    registry.gauge(f"bench.{key}",
                                   help=str(derived)).set(float(val))
            print(f"# {name}: {len(rows)} rows in "
                  f"{time.time() - t0:.1f}s", file=sys.stderr)
        except Exception as e:
            failures += 1
            entry["status"] = "failed"
            entry["error"] = f"{type(e).__name__}: {e}"
            print(f"# {name}: FAILED", file=sys.stderr)
            traceback.print_exc()
        entry["wall_s"] = round(time.time() - t0, 3)
        results.append(entry)
    if args.json:
        # the artifact is written even on failure: a red run's partial
        # trajectory is still a data point
        write_json(args.json, results, args.smoke,
                   round(time.time() - t_start, 3), registry,
                   argv=argv)
    if args.prom:
        with open(args.prom, "w") as f:
            f.write(registry.to_prometheus_text())
        print(f"# wrote {args.prom}: {len(registry.names())} series "
              f"(prometheus text)", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
