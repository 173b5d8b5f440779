"""repro.obs: unified observability plane for the control plane.

The paper's method is measurement — latency/bandwidth/tail behavior
under tiering — and this package gives the repro's own control plane
the same treatment:

- trace:    ring-bounded structured spans/events across the decision
            path (phase detect -> arbiter grant -> replan verdict ->
            move round -> executed deltas), exportable as JSONL and
            Chrome trace_event JSON; ``annotate`` puts a span on the
            profiler's clock, beside the device's operations
- registry: central counters/gauges/histograms with DDSketch-style
            streaming percentile sketches + Prometheus text exporter
- slo:      live rolling-window SLO monitors (TTFT / decode latency
            p50/p95/p99 vs thresholds) and the online burst-entry /
            steady lag-ratio monitor
- audit:    prediction ledger joining every planner forecast (move
            times, step costs, demand grants, phase predictions) with
            its realized outcome; residual histograms + drift detectors
- calibrate: cost-model calibrator fitting per-link latency/bandwidth
            corrections from probes and applying online EWMA scales
            from audit residuals
- qos:      interference-class QoS plane: per-tenant flow attribution
            (BlameLedger joining SLO violations to bottleneck links and
            noisy neighbors) and violation-predictive admission
            (ViolationPredictor priced on the class-aware contention
            model, audited as the ``qos.violation`` model)
"""
from .audit import DriftDetector, PredictionLedger, PredictionRecord
from .calibrate import (CostModelCalibrator, LinkCorrection, TierProbe,
                        measure_transfer_probes, probe_testbed)
from .qos import (BlameLedger, Excursion, QOS_VIOLATION_MODEL,
                  QOS_VIOLATION_TOLERANCE, ViolationPredictor)
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       PercentileSketch)
from .slo import LagRatioMonitor, SLOMonitor, SLOTarget
from .trace import (annotate, qos_chains, replan_chains, TraceEvent,
                    TraceRecorder)

__all__ = [
    "TraceEvent", "TraceRecorder", "annotate", "qos_chains",
    "replan_chains",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "PercentileSketch",
    "LagRatioMonitor", "SLOMonitor", "SLOTarget",
    "DriftDetector", "PredictionLedger", "PredictionRecord",
    "CostModelCalibrator", "LinkCorrection", "TierProbe",
    "measure_transfer_probes", "probe_testbed",
    "BlameLedger", "Excursion", "QOS_VIOLATION_MODEL",
    "QOS_VIOLATION_TOLERANCE", "ViolationPredictor",
]
