"""repro.serving: tier-aware continuous-batching serving subsystem.

The paper's LLM use case (Sec. IV-B) made online: a paged KV block
pool whose blocks live on memory tiers (kv_pool), §VI tiering runtimes
promoting hot blocks under a capacity budget (tiering), a
continuous-batching scheduler with admission control and
preemption-by-recompute (scheduler), a paged decode engine over the
Pallas decode-attention kernel (engine), and request/pool/migration
metrics (metrics), and MoE expert weights as tiered objects with
routing-driven heat and predictive prefetch (expert_pool).
"""
from .config import (ClusterOptions, ConfigError, ExpertOptions,
                     QoSOptions, ROUTER_POLICIES, TieringOptions)
from .engine import (check_paged_support, kind_tiers, ServingConfig,
                     ServingEngine, ServingReport)
from .expert_pool import ExpertCounters, ExpertPool
from .kv_pool import (FAST_KIND, KVBlock, KVBlockSpec, PagedKVPool,
                      PoolExhausted, spec_from_config, TieredKVCache)
from .metrics import percentile, RequestMetrics, ServingMetrics
from .scheduler import (AdmissionPlan, ContinuousBatchingScheduler,
                        plan_admission, Request, RequestState,
                        SchedulerConfig)
from .tiering import (KVBlockTierer, make_tiering_policy, POLICIES,
                      TieringStats)

__all__ = [
    "FAST_KIND", "KVBlock", "KVBlockSpec", "PagedKVPool", "PoolExhausted",
    "TieredKVCache", "spec_from_config",
    "KVBlockTierer", "POLICIES", "TieringStats", "make_tiering_policy",
    "AdmissionPlan", "ContinuousBatchingScheduler", "Request",
    "RequestState", "SchedulerConfig", "plan_admission",
    "RequestMetrics", "ServingMetrics", "percentile",
    "ServingConfig", "ServingEngine", "ServingReport",
    "check_paged_support", "kind_tiers",
    "ExpertCounters", "ExpertPool",
    "ClusterOptions", "ConfigError", "ExpertOptions", "QoSOptions",
    "ROUTER_POLICIES", "TieringOptions",
]
