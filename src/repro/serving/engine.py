"""ServingEngine: continuous batching over the paged, tiered KV pool.

The decode path is rebuilt around the block table instead of the
monolithic cache ``lm.decode_step`` uses: each iteration the running
requests' blocks are gathered from their tiers (async device_put, the
TieredArray discipline), the new token's K/V is scattered at each
sequence's own length, and attention runs through the Pallas
``kernels.decode_attention`` kernel — whose per-sequence ``kv_len``
masking is exactly what ragged continuous batches need.  Per-sequence
positions feed RoPE/learned embeddings, so sequences of different
lengths decode in one batch (the thing the one-shot FlexGenEngine
cannot do).

The serving loop puts spans on the profiler's clock
(``obs.trace.annotate``): ``serve.iteration`` around each iteration and
``serve.*`` spans around its phases, carrying the cumulative token and
KV transfer counts (``_counters``) as args; the pool adds ``kv.*``
spans.  The jitted programs are named ``serve_prefill``,
``serve_decode``, ``serve_decode_fused`` and ``serve_kv_stage`` (the
staged gather's layout of K and V for the decode step), and the decode
program's ops fall under the scopes ``attention``, ``kv_write``, ``mlp``
and ``head``.

Supported configs: attention-only patterns (optionally MoE) with
rope/learned/none positions and bf16 KV — the serving family of the
paper's Sec. IV-B study.  Hybrid SSM/RWKV decode stays on the one-shot
engine.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ModelConfig
from ..core.migration import MigrationExecutor
from ..core.tiers import GiB, MemoryTier, tpu_v5e_tiers
from ..kernels import ops
from ..launch import steps as steps_mod
from ..models import modules as M
from ..obs.trace import annotate
from . import config as config_mod
from ..telemetry import (AccessSampler, AccessTrace, AdaptiveReplanner,
                         PhaseDetector, ReplanConfig, SamplerConfig)
from .kv_pool import FAST_KIND, PagedKVPool, spec_from_config
from .metrics import ServingMetrics
from .scheduler import (ContinuousBatchingScheduler, plan_admission, Request,
                        RequestState, SchedulerConfig)
from .tiering import KVBlockTierer


def check_paged_support(cfg: ModelConfig) -> None:
    """Raise if the config can't run on the paged decode path."""
    for spec in cfg.pattern:
        if spec.kind != "attn" or spec.cross_attn:
            raise ValueError(
                f"{cfg.name}: paged serving supports attention-only "
                f"patterns (got {spec.kind}"
                f"{'+cross' if spec.cross_attn else ''}); use the "
                f"one-shot FlexGenEngine for hybrid architectures")
    if cfg.encoder_layers:
        raise ValueError(f"{cfg.name}: encoder-decoder serving is not "
                         "paged; use FlexGenEngine")
    if cfg.kv_cache_dtype != "bf16":
        raise ValueError(f"{cfg.name}: paged pool stores bf16 KV "
                         f"(got {cfg.kv_cache_dtype})")
    if cfg.pos_emb not in ("rope", "learned", "none"):
        raise ValueError(f"{cfg.name}: unsupported pos_emb "
                         f"{cfg.pos_emb!r} for paged decode")


# ---------------------------------------------------------------------- #
# Paged decode step (jitted once per engine; B and S_pad are static).     #
# ---------------------------------------------------------------------- #
def _paged_unit_fwd(cfg: ModelConfig, up, x, kv_k, kv_v, lengths,
                    block_k: int):
    """One repeating unit over the gathered block table.

    x: (B, 1, D); kv_k/kv_v: (n_attn, B, S_pad, KV, hd); lengths: (B,).
    Returns (x, new_k, new_v) with new_k/new_v (n_attn, B, KV, hd).
    """
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    barange = jnp.arange(B)
    new_ks, new_vs = [], []
    i_attn = 0
    for li, spec in enumerate(cfg.pattern):
        lp = up["layers"][li]
        ap = lp["attn"]
        with jax.named_scope("attention"):
            h = M.apply_norm(cfg.norm, lp["norm1"], x)
            q = h @ ap["wq"]
            k = h @ ap["wk"]
            v = h @ ap["wv"]
            if "bq" in ap:
                q = q + ap["bq"]
            if "bk" in ap:
                k = k + ap["bk"]
                v = v + ap["bv"]
            q = q.reshape(B, 1, H, hd)
            k = k.reshape(B, 1, KV, hd)
            v = v.reshape(B, 1, KV, hd)
            if cfg.pos_emb == "rope":
                pos = lengths[:, None]                 # per-seq positions
                q = M.apply_rope(q, pos, cfg.rope_theta, cfg.rotary_pct)
                k = M.apply_rope(k, pos, cfg.rope_theta, cfg.rotary_pct)
        ck, cv = kv_k[i_attn], kv_v[i_attn]            # (B, S_pad, KV, hd)
        k_tok = k[:, 0].astype(ck.dtype)
        v_tok = v[:, 0].astype(cv.dtype)
        with jax.named_scope("kv_write"):
            ck = ck.at[barange, lengths].set(k_tok)
            cv = cv.at[barange, lengths].set(v_tok)
        with jax.named_scope("attention"):
            att = ops.decode_attention(q[:, 0], ck, cv, lengths + 1,
                                       block_k=block_k)  # (B, H, hd)
            x = x + (att.reshape(B, 1, H * hd) @ ap["wo"])

        with jax.named_scope("mlp"):
            h = M.apply_norm(cfg.norm, lp["norm2"], x)
            if spec.moe:
                out, _ = M.moe_fwd(lp["moe"], h, top_k=cfg.top_k,
                                   capacity_factor=cfg.capacity_factor,
                                   n_groups=cfg.moe_groups, act=cfg.act)
            else:
                out = M.mlp_fwd(lp["mlp"], h, cfg.act)
            x = x + out
        new_ks.append(k_tok)
        new_vs.append(v_tok)
        i_attn += 1
    return x, jnp.stack(new_ks), jnp.stack(new_vs)


def _paged_decode(cfg: ModelConfig, block_k: int, params, tokens,
                  kv_k, kv_v, lengths):
    """tokens (B, 1) int32; kv_k/kv_v (U, n_attn, B, S_pad, KV, hd);
    lengths (B,) — tokens already cached per sequence.

    Returns (logits (B, V), new_k, new_v (U, n_attn, B, KV, hd))."""
    x = params["embed"][tokens[:, 0]].astype(jnp.bfloat16)[:, None]
    if cfg.pos_emb == "learned":
        x = x + params["pos_emb"][lengths].astype(x.dtype)[:, None]

    def body(carry, xs):
        up, kk, vv = xs
        h, nk, nv = _paged_unit_fwd(cfg, up, carry, kk, vv, lengths,
                                    block_k)
        return h, (nk, nv)

    x, (new_k, new_v) = lax.scan(body, x, (params["units"], kv_k, kv_v))
    with jax.named_scope("head"):
        x = M.apply_norm(cfg.norm, params["final_norm"], x)
        W = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = (x[:, 0] @ W.T).astype(jnp.float32)
    return logits, new_k, new_v


def _fused_unit_fwd(cfg: ModelConfig, up, x, k_pool, v_pool, block_tbl,
                    lengths, block_tokens: int):
    """One repeating unit on the fused tiered-gather path.

    x: (B, 1, D); k_pool/v_pool: (n_attn, num_blocks, bt, KV, hd) — the
    pool's *resident* layout, not a per-sequence staging copy; block_tbl
    (B, nb) int32 names each sequence's blocks in pool order.  Attention
    reads blocks straight from the pool via the scalar-prefetched table
    (kernels.tiered_gather) and folds the step's K/V in-kernel, so the
    gather+scatter the unfused path pays per iteration never happens.
    MoE layers run the fused expert FFN indexed by routed expert ids;
    the ids are returned (n_moe, B, K) so the ExpertPool can account
    per-expert heat.
    """
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    new_ks, new_vs, routed = [], [], []
    i_attn = 0
    for li, spec in enumerate(cfg.pattern):
        lp = up["layers"][li]
        h = M.apply_norm(cfg.norm, lp["norm1"], x)
        ap = lp["attn"]
        q = h @ ap["wq"]
        k = h @ ap["wk"]
        v = h @ ap["wv"]
        if "bq" in ap:
            q = q + ap["bq"]
        if "bk" in ap:
            k = k + ap["bk"]
            v = v + ap["bv"]
        q = q.reshape(B, 1, H, hd)
        k = k.reshape(B, 1, KV, hd)
        v = v.reshape(B, 1, KV, hd)
        if cfg.pos_emb == "rope":
            pos = lengths[:, None]
            q = M.apply_rope(q, pos, cfg.rope_theta, cfg.rotary_pct)
            k = M.apply_rope(k, pos, cfg.rope_theta, cfg.rotary_pct)
        k_tok = k[:, 0].astype(k_pool.dtype)
        v_tok = v[:, 0].astype(v_pool.dtype)
        att = ops.paged_decode_attention(
            q[:, 0], k_pool[i_attn], v_pool[i_attn], block_tbl,
            lengths, k_tok, v_tok, block_tokens=block_tokens)
        x = x + (att.reshape(B, 1, H * hd) @ ap["wo"])

        h = M.apply_norm(cfg.norm, lp["norm2"], x)
        if spec.moe:
            mp = lp["moe"]
            # token-choice top-k, weights renormalized over the chosen
            # experts — the moe_fwd routing, sans capacity/drop (decode
            # batches are far under capacity at serving scale)
            logits = h[:, 0].astype(jnp.float32) @ mp["router"]
            topw, topi = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   cfg.top_k)
            topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
            topi = topi.astype(jnp.int32)
            out = ops.fused_expert_ffn(h[:, 0], mp["w_gate"],
                                       mp["w_up"], mp["w_down"],
                                       topi, topw)[:, None]
            routed.append(topi)
        else:
            out = M.mlp_fwd(lp["mlp"], h, cfg.act)
        x = x + out
        new_ks.append(k_tok)
        new_vs.append(v_tok)
        i_attn += 1
    ids = (jnp.stack(routed) if routed
           else jnp.zeros((0, B, max(cfg.top_k, 1)), jnp.int32))
    return x, jnp.stack(new_ks), jnp.stack(new_vs), ids


def _fused_paged_decode(cfg: ModelConfig, block_tokens: int, params,
                        tokens, k_store, v_store, block_tbl, lengths):
    """tokens (B, 1) int32; k_store/v_store (U, n_attn, num_blocks, bt,
    KV, hd) — the pooled layout itself; block_tbl (B, nb) int32;
    lengths (B,).

    Returns (logits (B, V), new_k, new_v (U, n_attn, B, KV, hd),
    routed expert ids (U, n_moe, B, K))."""
    x = params["embed"][tokens[:, 0]].astype(jnp.bfloat16)[:, None]
    if cfg.pos_emb == "learned":
        x = x + params["pos_emb"][lengths].astype(x.dtype)[:, None]

    def body(carry, xs):
        up, kp, vp = xs
        h, nk, nv, ids = _fused_unit_fwd(cfg, up, carry, kp, vp,
                                         block_tbl, lengths,
                                         block_tokens)
        return h, (nk, nv, ids)

    x, (new_k, new_v, routed) = lax.scan(
        body, x, (params["units"], k_store, v_store))
    x = M.apply_norm(cfg.norm, params["final_norm"], x)
    W = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, 0] @ W.T).astype(jnp.float32)
    return logits, new_k, new_v, routed


def _stage_kv(kvs):
    """The batch's per-sequence (2, U, n_attn, S, KV, hd) payloads as
    the decode step's K and V, (U, n_attn, B, S, KV, hd) each, each
    written in one pass (stacking K and V apart needs no temporary
    copy of the batch)."""
    return (jnp.stack([kv[0] for kv in kvs], axis=2),
            jnp.stack([kv[1] for kv in kvs], axis=2))


def _program(fn: Callable, name: str):
    """``fn`` jitted under ``name``, the name its program carries in a
    device trace (a ``functools.partial`` has none of its own)."""
    fn.__name__ = name
    return jax.jit(fn)


# ---------------------------------------------------------------------- #
# Engine                                                                 #
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class ServingConfig:
    block_tokens: int = 16
    max_batch: int = 4
    max_context: int = 128            # prompt + generated cap per request
    policy: str = "tiering08"         # static | autonuma | tiering08 | tpp
    num_blocks: Optional[int] = None  # default: max_batch * blocks/seq
    fast_block_budget: Optional[int] = None   # default: half the pool
    slow_kind: str = "pinned_host"
    max_prefill_per_iter: int = 2
    migrate_every: int = 1
    # optional cost-model sizing: overrides num_blocks/fast budget/batch
    device_budget_bytes: Optional[int] = None
    host_budget_bytes: Optional[int] = None
    # telemetry + adaptive object-level re-interleaving (repro.telemetry):
    # sample_rate 1.0 = full instrumentation (smoke-scale traffic);
    # lower it toward PEBS-like rates for production-sized pools.
    adaptive: bool = False
    replan_every: int = 8   # iterations between replans (<= 0 disables)
    sample_rate: float = 1.0
    # predictive control plane (requires adaptive): plans are keyed by
    # the PhaseDetector's recurrence *signatures*, and when the
    # detector predicts a different phase next epoch the proven plan
    # cached for it is pre-staged (promotion-dominant deltas only) so
    # a recurring burst's first iteration runs on its placement
    predictive: bool = False
    # named repro.topology testbed: the scheduler budgets the shared
    # links KV gathers cross (contention-aware admission), and with
    # --adaptive the replanner prices the pool's memory kinds over that
    # machine's hop topology (path latency, bottleneck bandwidth,
    # shared-link move serialization)
    topology: Optional[str] = None
    # tenant namespace in the residency ledger (multi-tenant pools:
    # several engines/trainers sharing one ledger must use distinct
    # tenant names so the arbiter can split the fast tier among them)
    tenant: str = "serving"
    # observability plane (repro.obs): ring bound on the control-plane
    # trace, and optional p95 SLO thresholds (seconds) for TTFT and
    # inter-token decode latency — violations are counted live by the
    # rolling-window SLOMonitor and surfaced in the report
    trace_max_events: int = 65536
    slo_p95_ttft_s: Optional[float] = None
    slo_p95_decode_s: Optional[float] = None
    slo_p99_decode_s: Optional[float] = None
    # extreme-tail decode SLO (p99.9) and the rolling SLO window size;
    # p99.9 targets use a quantile-aware warmup (>= 1/(1-q) samples)
    # so violation_rate() is never judged off a handful of samples
    slo_p999_decode_s: Optional[float] = None
    slo_window: int = 512
    # fused tiered-gather decode: the pool keeps the pooled (stacked)
    # KV layout and attention reads blocks straight from it through a
    # scalar-prefetched block-index table (kernels.tiered_gather),
    # folding the new token in-kernel — the per-iteration gather_seq
    # staging copy and cache scatter disappear.  MoE layers run the
    # fused expert FFN indexed by routed ids (requires silu experts).
    fused_gather: bool = False
    # MoE expert tier residency (serving.expert_pool): experts become
    # tiered objects with routing-driven heat.  "lru" promotes by
    # recency (the expert-cache baseline); "predictive" additionally
    # prefetches the predicted next phase's hot experts.  Uses its own
    # residency namespace so KV arbitration grants are not diluted.
    expert_policy: Optional[str] = None
    expert_fast_fraction: float = 0.25   # share of experts fast-resident
    # interference-class QoS plane (requires topology + a decode SLO):
    # this tenant's gather flows are published tagged with their
    # interference class into a BlameLedger (tail excursions get joined
    # to their bottleneck link + noisy neighbor), and admission +
    # preemption switch from the flat link_efficiency_floor to a
    # ViolationPredictor pricing each candidate against every
    # registered tenant's predicted p99 (audited as ``qos.violation``)
    qos: bool = False
    # interference class this engine's KV gathers present (read for
    # decode-dominant serving; a prefill-heavy tenant may be write)
    qos_class: str = "read"
    # self-calibrating cost model (requires adaptive): fit the pool's
    # slow-tier bandwidth from a real transfer probe at startup and
    # keep correcting the planning tiers online from audit residuals,
    # so replan verdicts and migration pricing run on measured numbers
    calibrate: bool = False
    # ------------------------------------------------------------------
    # nested sections (serving.config): the grouped view of the flat
    # fields above.  Pass a section to configure by concern; pass the
    # flat kwargs and __post_init__ populates the sections — both
    # surfaces stay coherent either way.  ``cluster`` is new with the
    # multi-host plane and has no flat mirror.
    tiering: Optional["config_mod.TieringOptions"] = None
    qos_options: Optional["config_mod.QoSOptions"] = None
    experts: Optional["config_mod.ExpertOptions"] = None
    cluster: Optional["config_mod.ClusterOptions"] = None

    def __post_init__(self):
        config_mod.sync_sections(self)

    @classmethod
    def from_args(cls, args) -> "ServingConfig":
        """Build from a serve-CLI-shaped namespace, running every
        cross-field validation (``config.validate_args``) first.
        Raises :class:`~repro.serving.config.ConfigError` on any
        violated constraint — the CLI maps that to ``parser.error``.
        """
        config_mod.validate_args(args)
        get = lambda name, default=None: getattr(args, name, default)  # noqa: E731
        replicas = int(get("replicas", 1) or 1)
        cluster = None
        if replicas > 1 or get("router") is not None:
            cluster = config_mod.ClusterOptions(
                replicas=replicas,
                router=get("router") or "headroom-distance",
                shard_model=bool(get("shard_model", True)))
        return cls(
            block_tokens=get("block_tokens", 16),
            max_batch=get("batch", 4),
            max_context=(get("prompt_len", 32) + get("new_tokens", 16)
                         + get("block_tokens", 16)),
            policy=get("policy", "tiering08"),
            num_blocks=get("num_blocks"),
            fast_block_budget=get("fast_blocks"),
            adaptive=bool(get("adaptive")),
            replan_every=get("replan_every", 8),
            sample_rate=get("sample_rate", 1.0),
            predictive=bool(get("predictive")),
            calibrate=bool(get("calibrate")),
            topology=get("topology"),
            tenant=get("tenant") or "serving",
            slo_p95_ttft_s=get("slo_p95_ttft"),
            slo_p95_decode_s=get("slo_p95_decode"),
            slo_p99_decode_s=get("slo_p99_decode"),
            slo_p999_decode_s=get("slo_p999_decode"),
            slo_window=get("slo_window", 512),
            qos=bool(get("qos")),
            fused_gather=bool(get("fused_gather")),
            expert_policy=get("expert_policy"),
            expert_fast_fraction=get("expert_fast_frac", 0.25),
            cluster=cluster)


@dataclasses.dataclass
class ServingReport:
    summary: Dict[str, float]
    per_request: List[Tuple[int, Dict[str, float]]]
    tiering: Dict[str, int]
    policy: str
    telemetry: Dict[str, float] = dataclasses.field(default_factory=dict)
    slo: Dict[str, object] = dataclasses.field(default_factory=dict)


def kind_tiers(pool: PagedKVPool,
               fast_base: Optional[MemoryTier] = None,
               slow_base: Optional[MemoryTier] = None
               ) -> Dict[str, MemoryTier]:
    """MemoryTier descriptors for the pool's memory kinds, with
    capacities set from the pool's block budgets — what the adaptive
    replanner plans against.  ``fast_base``/``slow_base`` override the
    TPU defaults (e.g. a topology testbed's device-local tiers, whose
    hop latency the graph supplies)."""
    base = tpu_v5e_tiers()
    bn = pool.block_nbytes()
    if fast_base is None:
        fast_base = base["HBM"]
    if slow_base is None:
        slow_base = (base["HOST"] if pool.slow_kind == "pinned_host"
                     else base["HOST_UNPINNED"])
    fast = dataclasses.replace(
        fast_base, name=FAST_KIND,
        capacity_GiB=max(pool.fast_block_budget, 1) * bn / GiB)
    slow = dataclasses.replace(
        slow_base, name=pool.slow_kind, kind="host",
        capacity_GiB=max(pool.num_blocks, 1) * bn / GiB)
    return {FAST_KIND: fast, pool.slow_kind: slow}


class ServingEngine:
    """Continuous-batching serving over a tier-resident paged KV pool."""

    def __init__(self, cfg: ModelConfig, params,
                 serving: Optional[ServingConfig] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 ledger=None, pool_sharding=None):
        check_paged_support(cfg)
        self.cfg = cfg
        self.sv = sv = serving or ServingConfig()
        self.clock = clock
        self.params = params
        bt = sv.block_tokens
        self.max_seq_blocks = max(1, math.ceil(sv.max_context / bt))
        if sv.device_budget_bytes is not None:
            plan = plan_admission(
                cfg, bt, sv.max_context, sv.device_budget_bytes,
                sv.host_budget_bytes or 0, max_batch_cap=sv.max_batch)
            num_blocks, fast_budget = plan.total_blocks, plan.fast_blocks
            max_batch = plan.max_batch
        else:
            num_blocks = sv.num_blocks or sv.max_batch * self.max_seq_blocks
            fast_budget = (sv.fast_block_budget
                           if sv.fast_block_budget is not None
                           else max(1, num_blocks // 2))
            max_batch = sv.max_batch
        self.max_batch = max_batch
        if sv.fused_gather and any(
                s.moe for s in cfg.pattern) and cfg.act != "silu":
            raise ValueError(f"{cfg.name}: fused MoE decode needs silu "
                             "(gated) experts")
        spec = spec_from_config(cfg, bt)
        static = sv.policy in ("static", "none", "no_balance")
        # all tier occupancy flows through the (possibly shared)
        # residency ledger under this engine's tenant namespace; the
        # fused decode path needs the pooled layout it indexes into
        self.pool = PagedKVPool(
            num_blocks, bt, spec=spec, fast_block_budget=fast_budget,
            slow_kind=sv.slow_kind, default_kind=sv.slow_kind,
            ledger=ledger, tenant=sv.tenant, pooled=sv.fused_gather,
            sharding_fn=pool_sharding)
        self.ledger = self.pool.ledger
        self._static_split = static
        self.tierer = KVBlockTierer(self.pool, sv.policy)
        topo = None
        tb = None
        if sv.topology:
            from ..topology import build_topology
            tb = build_topology(sv.topology)
            topo = tb.graph
            # the pool's memory kinds ride the testbed's fast node
            # and its capacity-expander (CXL-class) node
            topo.alias_tier(tb.fast, FAST_KIND)
            topo.alias_tier(tb.capacity_tier, self.pool.slow_kind)
        self.topo = topo
        # observability plane: one tracer + registry + SLO monitor per
        # engine, all on the engine's virtual timebase (_now), created
        # before the components they instrument
        self._t0 = 0.0
        self._virtual_skew = 0.0
        self._step = 0
        from ..obs import (LagRatioMonitor, MetricsRegistry,
                           PredictionLedger, SLOMonitor, SLOTarget,
                           TraceRecorder)
        self.tracer = TraceRecorder(clock=self._now,
                                    max_events=sv.trace_max_events)
        self.registry = MetricsRegistry()
        # prediction audit plane: every control-plane forecast (step
        # costs, demand grants, phase predictions, move times) joins
        # its realized outcome here — always on, near-zero cost
        self.audit = PredictionLedger(registry=self.registry,
                                      tracer=self.tracer)
        slo_targets = []
        if sv.slo_p95_ttft_s is not None:
            slo_targets.append(SLOTarget("ttft", 0.95, sv.slo_p95_ttft_s))
        if sv.slo_p95_decode_s is not None:
            slo_targets.append(
                SLOTarget("decode_latency", 0.95, sv.slo_p95_decode_s))
        if sv.slo_p99_decode_s is not None:
            slo_targets.append(
                SLOTarget("decode_latency", 0.99, sv.slo_p99_decode_s))
        if sv.slo_p999_decode_s is not None:
            slo_targets.append(
                SLOTarget("decode_latency", 0.999, sv.slo_p999_decode_s))
        self.slo = SLOMonitor(slo_targets, clock=self._now,
                              registry=self.registry, tracer=self.tracer,
                              window=sv.slo_window)
        self.lag = LagRatioMonitor()
        self._lag_tokens = 0          # decode tokens at last epoch close
        self._lag_time = 0.0          # _now() at last epoch close
        # interference-class QoS plane: blame attribution + predictive
        # admission, both priced on the topology's class-aware
        # contention model
        self.blame = None
        self.predictor = None
        self._qos_last_key: Optional[int] = None
        if sv.qos:
            if topo is None:
                raise ValueError("qos requires a topology (the blame "
                                 "plane attributes violations to links)")
            decode_slo = sv.slo_p99_decode_s or sv.slo_p95_decode_s
            if decode_slo is None:
                raise ValueError("qos requires a decode SLO "
                                 "(slo_p99_decode_s or slo_p95_decode_s)")
            from ..obs import BlameLedger, ViolationPredictor
            self.blame = BlameLedger(topo, registry=self.registry,
                                     tracer=self.tracer, clock=self._now)
            self.predictor = ViolationPredictor(topo, blame=self.blame,
                                                audit=self.audit)
            self.predictor.set_target(sv.tenant, decode_slo)
            # every decode-latency excursion gets joined to its
            # bottleneck link + antagonist at firing time
            self.slo.add_violation_hook(
                lambda t, v, now: self.blame.on_violation(
                    sv.tenant, t.key, v, t.threshold_s, now=now)
                if t.metric == "decode_latency" else None)
        self.sched = ContinuousBatchingScheduler(
            self.pool, SchedulerConfig(
                max_batch=max_batch,
                max_prefill_per_iter=sv.max_prefill_per_iter,
                flow_class=sv.qos_class),
            topology=topo, tracer=self.tracer,
            predictor=self.predictor)
        self.metrics = ServingMetrics(registry=self.registry,
                                      slo=self.slo)
        # telemetry: the pool emits access events through a sampling
        # front-end; phase detection + (optionally) adaptive replanning
        # consume the shared trace, which also registers as this
        # tenant's namespace in the ledger (the arbiter reads it there)
        self.trace = AccessTrace()
        self.sampler = AccessSampler(
            self.trace, SamplerConfig(sample_rate=sv.sample_rate))
        self.pool.attach_telemetry(self.sampler)
        self.ledger.attach_trace(sv.tenant, self.trace)
        self.phases = PhaseDetector(self.trace)
        self.replanner: Optional[AdaptiveReplanner] = None
        if sv.predictive and not sv.adaptive:
            raise ValueError("predictive serving requires adaptive=True "
                             "(prediction pre-stages the replanner's "
                             "phase-cached plans)")
        if sv.calibrate and not sv.adaptive:
            raise ValueError("calibrate requires adaptive=True (the "
                             "corrections feed the replanner's cost "
                             "model)")
        self.calibrator = None
        if sv.adaptive:
            if tb is not None:
                tiers = kind_tiers(self.pool,
                                   fast_base=tb.tiers[tb.fast],
                                   slow_base=tb.tiers[tb.capacity_tier])
            else:
                tiers = kind_tiers(self.pool)
            if sv.calibrate:
                from ..obs import (CostModelCalibrator,
                                   measure_transfer_probes)
                self.calibrator = CostModelCalibrator(tiers, graph=topo)
                # startup fit: one real device->host transfer probe for
                # the pool's slow kind (the tier names ARE jax memory
                # kinds, so probes map directly); the fast (device)
                # tier keeps the builder numbers
                self.calibrator.fit_probes(measure_transfer_probes(
                    kinds=(self.pool.slow_kind,), n_mb=16, iters=2))
            executor = MigrationExecutor(tiers,
                                         move_fn=self._move_seq_blocks,
                                         topology=topo)
            self.replanner = AdaptiveReplanner(
                self.trace, tiers, FAST_KIND,
                cfg=ReplanConfig(replan_every=max(sv.replan_every, 1),
                                 window_epochs=max(sv.replan_every, 1)),
                executor=executor,
                default_tier=self.pool.slow_kind,
                topology=topo,
                ledger=self.ledger, tenant=sv.tenant,
                tracer=self.tracer, audit=self.audit,
                calibrator=self.calibrator)
            self.replanner.executor.tracer = self.tracer
            self.replanner.executor.audit = self.audit
            self.replanner.executor.calibrator = self.calibrator
            self.replanner.executor.recalibrate()
        # predictive engines run the full control plane in-engine: a
        # predictive TierBudgetArbiter rebalances this tenant's
        # fast-tier grant each replan epoch (capacity = the configured
        # fast-block budget, so single-tenant grants can never exceed
        # what the pool was sized for), and replan deltas defer to a
        # MoveScheduler round so the trace shows the scheduled batch
        self.arbiter = None
        self.movesched = None
        if sv.predictive:
            from ..pool import MoveScheduler, TierBudgetArbiter
            self.arbiter = TierBudgetArbiter(
                self.ledger, FAST_KIND,
                capacity_bytes=fast_budget * self.pool.block_nbytes(),
                objective="fair_share", predictive=True,
                tracer=self.tracer, audit=self.audit)
            self.movesched = MoveScheduler(
                self.replanner.executor, self.ledger, tracer=self.tracer)
            self.movesched.audit = self.audit
            self.movesched.calibrator = self.calibrator
            self.replanner.move_scheduler = self.movesched
        # MoE expert tier residency: every (layer, expert) weight block
        # becomes a tiered object with routing-driven heat, sharing the
        # cross-tenant move scheduler when one exists but keeping its
        # own residency namespace (so the KV arbiter's fair-share grant
        # is not split against expert bytes)
        self.expert_pool = None
        self._moe_per_unit = sum(1 for s in cfg.pattern if s.moe)
        if sv.expert_policy:
            from .expert_pool import (expert_nbytes_from_config,
                                      ExpertPool, moe_layers_from_config)
            n_moe = moe_layers_from_config(cfg)
            if n_moe == 0:
                raise ValueError(f"{cfg.name}: expert_policy set but "
                                 "the model has no MoE layers")
            total = n_moe * cfg.n_experts
            budget = max(1, int(round(total * sv.expert_fast_fraction)))
            self.expert_pool = ExpertPool(
                n_moe, cfg.n_experts, expert_nbytes_from_config(cfg),
                fast_expert_budget=budget, policy=sv.expert_policy,
                tenant=f"{sv.tenant}.experts", slow_kind=sv.slow_kind,
                movesched=self.movesched, tracer=self.tracer)
        self._prefill = _program(steps_mod.make_prefill_step(cfg),
                                 "serve_prefill")
        self._decode = _program(functools.partial(_paged_decode, cfg, bt),
                                "serve_decode")
        self._kv_stage = _program(_stage_kv, "serve_kv_stage")
        self._decode_fused = (
            _program(functools.partial(_fused_paged_decode, cfg, bt),
                     "serve_decode_fused")
            if sv.fused_gather else None)
        self._next_rid = 0
        self._prefill_tokens = 0      # prompt tokens prefilled

    # ------------------------------------------------------------------ #
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               arrival_s: float = 0.0, priority: float = 0.0) -> int:
        """Queue one request; returns its request id.  ``priority``
        orders budget preemption (lowest evicted first)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = min(max_new_tokens,
                      self.sv.max_context - prompt.shape[0])
        if max_new <= 0:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens leaves no room "
                f"under max_context={self.sv.max_context}")
        need = self.pool.blocks_for_tokens(prompt.shape[0] + 1)
        margin = self.sched.cfg.admission_margin_blocks
        if need + margin > self.pool.num_blocks:
            raise ValueError(
                f"prompt needs {need} blocks (+{margin} margin) but the "
                f"pool only has {self.pool.num_blocks}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                      arrival_s=arrival_s, priority=priority)
        self.sched.submit(req)
        self.metrics.on_submit(rid, arrival_s, prompt.shape[0])
        return rid

    def submit_trace(self, prompts: Sequence[np.ndarray],
                     max_new_tokens: int,
                     arrivals: Optional[Sequence[float]] = None
                     ) -> List[int]:
        arrivals = arrivals or [0.0] * len(prompts)
        return [self.submit(p, max_new_tokens, a)
                for p, a in sorted(zip(prompts, arrivals),
                                   key=lambda pa: pa[1])]

    # ------------------------------------------------------------------ #
    def _alloc_kind(self) -> Optional[str]:
        """Per-block allocation kind (passed as a callable to the pool).

        Static policy: a fixed split — fast at the budget's share of the
        pool, interleaved per block, never migrated (the one-shot
        engine's kv_shares, online).  Dynamic policies: first-touch in
        the slow tier; promotion earns fast residency from observed
        heat.
        """
        pool = self.pool
        if self._static_split:
            target = pool.fast_block_budget / max(pool.num_blocks, 1)
            if pool.fast_used() < pool.fast_block_budget and \
                    pool.fast_used() < target * (pool.used_block_count()
                                                 + 1):
                return FAST_KIND
        return None           # pool default (slow kind)

    def _counters(self) -> Dict[str, int]:
        """The cumulative counts a hot-path span carries as its args:
        tokens handed over, prompt tokens prefilled, and the pool's KV
        transfers between host memory and the device."""
        c = self.pool.counters
        return dict(tokens_out=self.metrics.decode_tokens,
                    prefill_tokens=self._prefill_tokens,
                    kv_h2d_bytes=c.h2d_bytes, kv_d2h_bytes=c.d2h_bytes,
                    kv_h2d_puts=c.h2d_puts, kv_d2h_puts=c.d2h_puts,
                    kv_h2d_calls=c.h2d_calls, kv_d2h_calls=c.d2h_calls)

    def _do_prefill(self, req: Request, now: float) -> None:
        toks = req.prefill_tokens()[None]          # (1, L)
        L = toks.shape[1]
        with annotate("serve.prefill", rid=req.rid, tokens=L):
            need = self.pool.blocks_for_tokens(L + 1)
            if not self.pool.can_alloc(need):
                for v in self.sched.preempt_for_blocks(need, protect=req):
                    self.metrics.on_preempt(v.rid, now)
            if req.state is not RequestState.RUNNING:
                return                 # pool too tight: preempted itself
            logits, cache = self._prefill(self.params,
                                          {"tokens": np.asarray(toks)})
            self._prefill_tokens += L
            self.pool.write_prefill(req.rid, cache["kv_k"][:, :, 0],
                                    cache["kv_v"][:, :, 0], L,
                                    kind=self._alloc_kind)
            self.metrics.on_admit(req.rid, now)
            tok = int(np.asarray(jnp.argmax(logits[0])))
            req.out_tokens.append(tok)
            self.metrics.on_token(req.rid, self._now())
            if req.done:
                self.sched.finish(req)
                self.metrics.on_finish(req.rid, self._now(),
                                       req.preemptions)

    def _ensure_tail_blocks(self) -> None:
        """Every running request needs a block for its next KV write."""
        for req in list(self.sched.running):
            if req.state is not RequestState.RUNNING:
                continue               # evicted by an earlier iteration
            n = self.pool.seq_len[req.rid]
            if n % self.pool.block_tokens != 0:
                continue
            if n // self.pool.block_tokens < len(
                    self.pool.table[req.rid]):
                continue
            if not self.pool.can_alloc(1):
                for v in self.sched.preempt_for_blocks(1, protect=req):
                    self.metrics.on_preempt(v.rid, self._now())
            if req.state is not RequestState.RUNNING:
                continue               # preempted itself
            self.pool.alloc(req.rid, 1, kind=self._alloc_kind)

    def _gather_kv(self, batch) -> tuple:
        """The decode step's KV inputs for ``batch``, padded to the
        fixed batch shape (one compile).  Fused tiered-gather decode:
        the pooled stores and the batch's block-index tables, which the
        jitted step reads through — no staging copy.  Staged decode:
        each sequence's blocks gathered onto the device, one
        (2, U, n_attn, S_pad, KV, hd) buffer a sequence, laid out by
        ``serve_kv_stage`` as (U, n_attn, B, S_pad, KV, hd) for K and
        for V."""
        n_pad = self.max_batch - len(batch)
        if self._decode_fused is not None:
            tbl, _ = self.pool.gather_tables([r.rid for r in batch],
                                             self.max_seq_blocks)
            if n_pad:
                tbl = np.concatenate(
                    [tbl, np.zeros((n_pad, tbl.shape[1]), np.int32)])
            return self.pool.k_store, self.pool.v_store, tbl
        kvs = [self.pool.gather_seq(req.rid, self.max_seq_blocks)
               for req in batch]
        if n_pad:
            kvs += [jnp.zeros_like(kvs[0], device=kvs[0].sharding)] * n_pad
        return self._kv_stage(kvs)

    def _record_routing(self, n_seqs: int, routed) -> None:
        """Routed expert ids (U, n_moe, B, K) feed per-expert heat."""
        ids = np.asarray(routed)
        for u in range(ids.shape[0]):
            for m in range(ids.shape[1]):
                gl = u * self._moe_per_unit + m
                for i in range(n_seqs):
                    self.expert_pool.record_routing(gl, ids[u, m, i],
                                                    self._step)

    def _decode_iteration(self, now: float) -> None:
        batch = list(self.sched.running)
        if not batch:
            return
        n_pad = self.max_batch - len(batch)
        # host inputs go in as numpy: jit places them with the params,
        # where a jnp array would first land on the default device
        tokens = np.asarray([r.out_tokens[-1] for r in batch] + [0] * n_pad,
                            np.int32)[:, None]
        lengths = np.asarray([self.pool.seq_len[r.rid] for r in batch]
                             + [0] * n_pad, np.int32)
        blocks = sum(len(self.pool.table.get(r.rid, ())) for r in batch)
        with annotate("kv.gather", seqs=len(batch), blocks=blocks,
                      **self._counters()):
            kv = self._gather_kv(batch)
        routed = None
        with annotate("serve.decode", **self._counters()):
            if self._decode_fused is not None:
                logits, new_k, new_v, routed = self._decode_fused(
                    self.params, tokens, *kv, lengths)
            else:
                logits, new_k, new_v = self._decode(self.params, tokens,
                                                    *kv, lengths)
        with annotate("serve.sync"):
            next_toks = np.asarray(jnp.argmax(logits, axis=-1))
        now_tok = self._now()
        with annotate("serve.deliver"):
            if (routed is not None and self.expert_pool is not None
                    and routed.shape[1]):
                self._record_routing(len(batch), routed)
            for i, req in enumerate(batch):
                # new_k/new_v (U, n_attn, B, KV, hd) stay on the device
                self.pool.append_token(req.rid, new_k[:, :, i],
                                       new_v[:, :, i])
                self.pool.touch_seq(req.rid, self._step)
                req.out_tokens.append(int(next_toks[i]))
                self.metrics.on_token(req.rid, now_tok)
                if req.done:
                    self.sched.finish(req)
                    self.metrics.on_finish(req.rid, now_tok,
                                           req.preemptions)

    # ------------------------------------------------------------------ #
    def _move_seq_blocks(self, obj: str, src: str, dst: str,
                         nbytes: int) -> int:
        """MigrationExecutor move_fn: realize an object-level byte move
        as pool-block migrations.  Returns bytes actually moved (the
        fast-block budget may deny promotions)."""
        if not obj.startswith("seq"):
            return 0
        try:
            sid = int(obj[3:])
        except ValueError:
            return 0
        bn = self.pool.block_nbytes()
        want = int(round(nbytes / max(bn, 1)))
        moved = 0
        for b in self.pool.seq_blocks(sid):
            if moved >= want:
                break
            if b.kind == src and self.pool.migrate(b.bid, dst):
                moved += 1
        return moved * bn

    def _replan_step(self) -> None:
        """One telemetry epoch: close the bucket, track phases, and (in
        adaptive mode) attempt an object-level replan over live
        sequences.  Predictive mode keys the plan cache by recurrence
        signature and pre-stages the proven plan of a predicted
        next-epoch phase during the current one's slack."""
        self.sampler.advance_epoch()
        self.phases.update()
        # live lag monitor: one (phase, tokens, time) sample per epoch
        now = self._now()
        self.lag.observe_epoch(str(self.phases.label),
                               self.metrics.decode_tokens
                               - self._lag_tokens,
                               now - self._lag_time)
        self._lag_tokens = self.metrics.decode_tokens
        self._lag_time = now
        self.tracer.event("phase.update", cat="phase",
                          epoch=self._step, label=str(self.phases.label),
                          shifts=len(self.phases.shifts))
        if self.expert_pool is not None:
            # close the expert heat epoch and run promote/demote (and,
            # under the predictive policy, next-phase prefetch)
            self.expert_pool.step(self._step)
        if self.blame is not None:
            # keep this tenant's class-tagged offered flows current in
            # the shared blame book *before* the SLO check, so a firing
            # violation attributes against fresh loads
            self.blame.publish_flows(self.sv.tenant,
                                     self.sched._running_flows(),
                                     now=now)
            if self.expert_pool is not None:
                # expert-gather traffic rides the same tier link as KV
                # gathers; publish it class-tagged under the expert
                # namespace so blame can split demand reads from
                # optional prefetch bytes
                self.blame.publish_flows(
                    self.expert_pool.tenant,
                    self.expert_pool.gather_flows(self.topo), now=now)
        if self.slo.targets and self._step % 16 == 0:
            self.slo.check()
            if self.predictor is not None:
                self._qos_audit_step()
        if (self.replanner is None or self.sv.replan_every <= 0
                or self._step == 0
                or self._step % self.sv.replan_every != 0):
            return
        if self.arbiter is not None:
            self.arbiter.rebalance(epoch=self._step)
        if self.calibrator is not None:
            # refresh the replanner's planning view from whatever online
            # scale corrections the audit loop accumulated this epoch
            self.replanner.recalibrate()
        bn = self.pool.block_nbytes()
        nbytes = {f"seq{sid}": len(tbl) * bn
                  for sid, tbl in self.pool.table.items() if tbl}
        if not nbytes:
            return
        try:
            if self.sv.predictive and self.phases.signature is not None:
                cur = self.phases.expected_signature(1)
                nxt = self.phases.expected_signature(2)
                if nxt is not None and nxt != cur:
                    d = self.replanner.prefetch_phase(self._step, nbytes,
                                                      nxt)
                    if d is not None:
                        return
                self.replanner.maybe_replan(self._step, nbytes,
                                            force=True, phase=cur)
                return
            # phase-conditioned plan cache: recurring detector labels
            # (prefill-heavy vs decode-heavy mixes) reuse their plan
            self.replanner.maybe_replan(self._step, nbytes, force=True,
                                        phase=self.phases.label)
        finally:
            # deferred applies must land this epoch: flush the move
            # round so the realized residency is adopted before the
            # next iteration reads the ledger
            if self.movesched is not None and self.movesched.has_pending:
                self.movesched.flush(epoch=self._step)

    def _qos_audit_step(self) -> None:
        """One predict/realize audit cycle for the ``qos.violation``
        model: join the previous check's tail forecast with the window
        p99 measured now, refresh the online baseline, and file the
        forecast for the next check from the live flow set."""
        sv = self.sv
        q = 0.99 if sv.slo_p99_decode_s is not None else 0.95
        observed = self.slo.quantile("decode_latency", q)
        if observed is None:
            return
        if self._qos_last_key is not None:
            self.predictor.realize(self._qos_last_key, sv.tenant,
                                   observed)
            self._qos_last_key = None
        self.predictor.observe_p99(sv.tenant, observed)
        pred = self.predictor.file_prediction(
            self._step, sv.tenant,
            extra_flows=self.sched._running_flows(),
            exclude=sv.tenant, epoch=self._step)
        if pred is not None:
            self._qos_last_key = self._step

    def telemetry_summary(self) -> Dict[str, float]:
        out: Dict[str, float] = {
            "trace_events": float(self.trace.total_events),
            "profiling_samples": float(self.sampler.samples),
            "profiling_overhead_s": self.sampler.overhead_s,
            "phase_shifts": float(len(self.phases.shifts)),
            "link_deferrals": float(self.sched.link_deferrals),
            "budget_preemptions": float(self.sched.budget_preemptions),
            "qos_deferrals": float(self.sched.qos_deferrals),
            "slo_preemptions": float(self.sched.slo_preemptions),
            "ledger_migrated_bytes": float(
                self.ledger.counters.migrated_bytes),
        }
        if self.replanner is not None:
            out.update(self.replanner.summary())
        if self.expert_pool is not None:
            out.update(self.expert_pool.summary())
        if self.movesched is not None:
            for k, v in self.movesched.summary().items():
                out[f"movesched.{k}"] = v
        if self.arbiter is not None:
            out["arbiter_rebalances"] = float(len(self.arbiter.decisions))
            out["arbiter_predicted_grants"] = float(
                self.arbiter.predicted_grants)
        lag = self.lag.ratio()
        if lag is not None:
            out["live_burst_entry_ratio"] = float(lag)
        out["trace_recorded_events"] = float(len(self.tracer))
        out["trace_dropped_events"] = float(self.tracer.dropped)
        if self.blame is not None:
            out.update(self.blame.summary())
        out.update(self.audit.summary())
        if self.calibrator is not None:
            out.update(self.calibrator.summary())
        return out

    def audit_report(self) -> Dict[str, object]:
        """Structured prediction-audit artifact (the ``--audit-out``
        payload): per-model residual stats plus, when calibration is
        on, the fitted/online correction state."""
        out: Dict[str, object] = {"audit": self.audit.report()}
        if self.calibrator is not None:
            out["calibration"] = self.calibrator.summary()
        return out

    # ------------------------------------------------------------------ #
    def _now(self) -> float:
        """Trace time: wall clock since run() start plus the virtual
        fast-forward over idle arrival gaps.  Every metrics timestamp
        uses this base so TTFT/latency stay comparable to the synthetic
        ``arrival_s`` values."""
        return self.clock() - self._t0 + self._virtual_skew

    def _iteration(self) -> None:
        """One pass of the loop: schedule, prefill what was admitted,
        decode the batch, tier and replan.  An idle pass fast-forwards
        the arrival clock and counts no step."""
        now = self._now()
        with annotate("serve.schedule", admitted=self.metrics.prefills):
            # an arbiter may have shrunk this tenant's fast budget in
            # the shared ledger since the last iteration: enforce it
            # before admitting new work (freed blocks re-admit victims)
            for v in self.sched.preempt_over_budget():
                self.metrics.on_preempt(v.rid, now)
            # predictive QoS: back off while any registered tenant's
            # predicted tail exceeds its target under our live flows
            for v in self.sched.preempt_predicted_violation():
                self.metrics.on_preempt(v.rid, now)
            admitted = self.sched.admit(now_s=now)
        if not admitted and not self.sched.running:
            # idle: fast-forward the arrival clock (synthetic traces)
            pending = [r.arrival_s for r in self.sched.waiting]
            skip = max(min(pending) - now, 0.0) if pending else 0.0
            if skip <= 0.0:
                raise RuntimeError(
                    "scheduler stalled: waiting requests cannot be "
                    "admitted into an empty pool (pool too small)")
            self._virtual_skew += skip
            return
        for req in admitted:
            self._do_prefill(req, now)
        with annotate("kv.ensure_tail"):
            self._ensure_tail_blocks()
        self._decode_iteration(now)
        if self.sv.migrate_every and \
                self._step % self.sv.migrate_every == 0:
            with annotate("tier.step",
                          migrated=self.pool.counters.migrated_bytes):
                self.tierer.step(
                    [r.rid for r in self.sched.running], self._step)
        with annotate("serve.control"):
            self._replan_step()
        self.metrics.on_iteration(self.pool.used_block_count(),
                                  len(self.sched.running))
        self._step += 1

    def run(self, max_iterations: int = 10_000) -> ServingReport:
        """Drive the trace to completion; returns the serving report."""
        self._t0 = self.clock()
        self._virtual_skew = 0.0
        while self.sched.active and self._step < max_iterations:
            with jax.profiler.StepTraceAnnotation(
                    "serve.iteration", step_num=self._step,
                    running=len(self.sched.running),
                    waiting=len(self.sched.waiting), **self._counters()):
                self._iteration()
        tstats = self.tierer.stats.as_dict()
        # adaptive replan moves also migrate pool blocks; surface them in
        # the tiering counters the report exposes
        tstats["migrated_bytes"] = self.pool.counters.migrated_bytes
        if self.slo.targets:
            self.slo.check()           # final window evaluation
        summary = self.metrics.summary(tstats)
        telemetry = self.telemetry_summary()
        # publish the run's aggregates into the central registry so a
        # --metrics-out export carries engine + ledger + control-plane
        # state alongside the streaming histograms
        self.registry.set_gauges(summary, prefix="serving.summary")
        self.registry.set_gauges(telemetry, prefix="serving.telemetry")
        self.ledger.publish(self.registry)
        self.registry.set_gauges(self.audit.summary())
        if self.calibrator is not None:
            self.calibrator.publish(self.registry)
        slo = self.slo.summary()
        if self.blame is not None:
            slo["blame"] = self.blame.blame_report()
        return ServingReport(
            summary=summary,
            per_request=self.metrics.per_request_rows(),
            tiering=tstats, policy=self.tierer.policy_name,
            telemetry=telemetry, slo=slo)
