"""Multi-host session routing vs capacity-blind baselines (repro.cluster).

A pod of hosts (``multi_host_pod``) serves a skewed session mix: most
sessions are small, but every few arrivals a "whale" carries several
times their KV footprint.  A session's KV must live on its replica for
its whole lifetime, and every decode step sweeps it — so placement is
a *memory-capacity* bet: KV beyond a host's fast tier spills to its
CXL-class expander and pays the paper's Fig.-2-style latency/bandwidth
delta on every subsequent token.

Routing policies under test (the real ``SessionRouter``):

  headroom-distance   fast-tier headroom first, front-end ICI distance
                      as the tiebreak — the topology-aware policy;
  least-loaded        session count, blind to bytes;
  round-robin /       capacity-blind baselines: a whale lands wherever
  random              the cursor or the dice say.

Execution is priced analytically (multi_tenant_bench idiom): a replica
decodes its active sessions memory-bound — each iteration costs the sum
of its active sessions' KV sweep times (fast bytes at fast bandwidth,
spilled bytes at CXL bandwidth, plus the per-token front-end distance)
— and replicas run in parallel, so cluster throughput is total tokens
over the slowest replica's makespan, and a session's latency is the
iteration time it accumulates until it finishes.

Acceptance (the tentpole's headline):

  * ``cluster.routing_speedup`` — headroom-distance aggregate tokens/s
    over round-robin — must be >= 1.1x at equal capacity, and the
    victim p95 (worst-session completion) must not regress;
  * namespace conservation: per-replica ledger aggregates
    (``host<i>/*``) sum *exactly* to the fleet aggregate (``*/*``)
    for every tier — the hierarchical-key invariant;
  * the plane arbiter's per-replica grants never exceed any host's
    physical fast capacity (the hierarchical water-fill's point).

A second segment runs the real ``ClusterPlane`` (mesh-sharded engines,
shared ledger, merged trace) end-to-end on a smoke model — on CI's
forced 8-device host platform this exercises true multi-device
placement; a host with one device runs one replica, as replicas never
share a device.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.cluster import SessionRequest, SessionRouter
from repro.core import GiB
from repro.pool import ResidencyLedger, TierBudgetArbiter
from repro.topology import ROUTER_NODE, multi_host_pod

N_HOSTS = 4
POLICIES = ("headroom-distance", "least-loaded", "round-robin", "random")

# heavy-tailed session KV footprints (lognormal): most sessions are
# small, the tail carries whales several GiB deep — the regime where
# count-balanced placement is NOT byte-balanced
KV_SCALE_GIB = 0.55
KV_SIGMA = 1.1
# session length correlates with context footprint: a whale decodes
# longer too, so misplacing it hurts twice
TOKENS_BASE, TOKENS_PER_GIB = 192, 160
# per-host fast capacity as a share of total KV demand: the fleet can
# *almost* hold the mix fast if — and only if — placement balances
# bytes; capacity-blind policies overload one host's fast tier
FAST_CAP_SHARE = 0.24


@dataclasses.dataclass(frozen=True)
class Session:
    sid: str
    kv_bytes: int
    tokens: int


def synth_sessions(n: int, seed: int = 0) -> List[Session]:
    """Deterministic heavy-tailed arrivals."""
    rs = np.random.RandomState(seed)
    sizes = rs.lognormal(mean=0.0, sigma=KV_SIGMA, size=n) \
        * KV_SCALE_GIB * GiB
    return [Session(f"s{i}", int(b),
                    TOKENS_BASE + int(b / GiB * TOKENS_PER_GIB))
            for i, b in enumerate(sizes)]


@dataclasses.dataclass
class RoutingResult:
    policy: str
    agg_tok_s: float
    victim_p95_s: float
    spilled_bytes: int
    routed: Dict[str, int]


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    ys = sorted(xs)
    idx = min(int(round(q / 100.0 * (len(ys) - 1))), len(ys) - 1)
    return ys[idx]


def simulate_routing(policy: str, sessions: List[Session],
                     testbed=None, seed: int = 1,
                     fast_cap_bytes: Optional[int] = None
                     ) -> RoutingResult:
    """Place the mix with the real router, then price the decode."""
    tb = testbed or multi_host_pod(N_HOSTS)
    if fast_cap_bytes is None:
        fast_cap_bytes = int(
            FAST_CAP_SHARE * sum(s.kv_bytes for s in sessions))
    fast_cap = {h: fast_cap_bytes for h in tb.hosts}
    placed: Dict[str, List[Session]] = {h: [] for h in tb.hosts}
    used: Dict[str, int] = {h: 0 for h in tb.hosts}

    router = SessionRouter(policy, seed=seed)
    for h in tb.hosts:
        router.register(
            h, distance_ns=tb.distance_ns(ROUTER_NODE, h),
            headroom_fn=lambda h=h: fast_cap[h] - used[h],
            load_fn=lambda h=h: len(placed[h]))
    # shared namespaced ledger mirrors every placement — hierarchical
    # keys <host>/serving/<session>, per-host fast + expander tiers
    ledger = ResidencyLedger(tb.tiers)
    for h in tb.hosts:
        ledger.register_tenant(f"{h}/serving")

    for s in sessions:
        req = SessionRequest(session_id=s.sid, prompt_tokens=0,
                             new_tokens=s.tokens,
                             kv_bytes_hint=s.kv_bytes)
        h = router.route(req)
        # `used` is live, so the router's own pending-bytes reservation
        # would double-count every placement — drop it immediately
        router.drain_pending()
        fast = min(s.kv_bytes, fast_cap[h] - used[h])
        spill = s.kv_bytes - fast
        used[h] += fast
        placed[h].append(s)
        ledger.register(
            f"{h}/serving", s.sid,
            {tb.fast_tier[h]: fast, tb.capacity_tier[h]: spill},
            origin="router")

    # namespace conservation: per-replica rollups sum EXACTLY to the
    # fleet aggregate, tier by tier — no double counting, no leakage
    fleet = ledger.aggregate("*/*")
    by_host = [ledger.aggregate(f"{h}/*") for h in tb.hosts]
    for tier in fleet:
        assert fleet[tier] == sum(a.get(tier, 0) for a in by_host), (
            f"namespace aggregation leaked on {tier}")
    assert sum(sum(a.values()) for a in by_host) == \
        sum(s.kv_bytes for s in sessions)

    # decode pricing: memory-bound iterations, replicas in parallel
    completion: List[float] = []
    makespans: List[float] = []
    total_tokens = 0
    spilled = 0
    for h in tb.hosts:
        fast_bw = tb.tiers[tb.fast_tier[h]].peak_bw_GBps * 1e9
        slow_bw = tb.tiers[tb.capacity_tier[h]].peak_bw_GBps * 1e9
        dist_s = tb.distance_ns(ROUTER_NODE, h) * 1e-9
        # per-session sweep time under this host's fast/spill split
        # (allocation order = arrival order, same as the ledger's)
        room = fast_cap[h]
        sweeps, left = [], []
        for s in placed[h]:
            fast = min(s.kv_bytes, room)
            room -= fast
            spill = s.kv_bytes - fast
            spilled += spill
            sweeps.append(fast / fast_bw + spill / slow_bw + dist_s)
            left.append(s.tokens)
            total_tokens += s.tokens
        t = 0.0
        while any(n > 0 for n in left):
            t += sum(sw for sw, n in zip(sweeps, left) if n > 0)
            for i, n in enumerate(left):
                if n > 0:
                    left[i] = n - 1
                    if left[i] == 0:
                        completion.append(t)
        makespans.append(t)
    agg = total_tokens / max(max(makespans), 1e-12)
    return RoutingResult(policy, agg, _percentile(completion, 95),
                         spilled, router.routed_counts())


def check_plane_arbiter(sessions: List[Session]) -> int:
    """The hierarchical split: per-replica grants respect per-host
    physical fast capacity.  Returns the number of granted tenants."""
    tb = multi_host_pod(N_HOSTS)
    fast_cap = {h: int(tb.tiers[tb.fast_tier[h]].capacity_GiB * GiB)
                for h in tb.hosts}
    # one logical "serving" tenant per host + one flat legacy tenant —
    # the degenerate default group must coexist with replica groups
    tiers = dict(tb.tiers)
    from repro.core import paper_system
    tiers["LDRAM"] = paper_system("A")["LDRAM"]
    ledger = ResidencyLedger(tiers)
    for h in tb.hosts:
        ledger.register_tenant(f"{h}/serving")
    demand = {h: 0 for h in tb.hosts}
    for i, s in enumerate(sessions):
        h = tb.hosts[i % len(tb.hosts)]
        ledger.register(f"{h}/serving", s.sid,
                        {tb.fast_tier[h]: s.kv_bytes})
        demand[h] += s.kv_bytes
    # the plane splits ONE logical fast-tier pool; per-host tier names
    # are aliases of it, so capacity is the sum with per-replica caps
    arb = TierBudgetArbiter(
        ledger, tb.fast_tier[tb.hosts[0]],
        capacity_bytes=sum(fast_cap.values()),
        replica_capacity=fast_cap, window_epochs=None)
    grants = arb.split(arb.demands())
    for h in tb.hosts:
        granted = sum(g for name, g in grants.items()
                      if name.startswith(f"{h}/"))
        assert granted <= fast_cap[h], (
            f"arbiter granted {granted} to {h} over its physical "
            f"fast capacity {fast_cap[h]}")
    return len(grants)


def run_plane_smoke(registry=None) -> List[Tuple[str, float, str]]:
    """The real ClusterPlane end-to-end on a smoke model."""
    import jax

    from repro.cluster import ClusterPlane
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serving import ServingConfig

    cfg = get_smoke_config("llama3-8b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # a replica needs a device of its own
    plane = ClusterPlane(
        cfg, params, n_replicas=min(2, len(jax.devices())),
        serving=ServingConfig(block_tokens=8, max_batch=2,
                              max_context=32, policy="tiering08"))
    rs = np.random.RandomState(0)
    for i in range(4):
        plane.submit(rs.randint(0, cfg.vocab, (8,)).astype(np.int32),
                     4, arrival_s=0.005 * i)
    rep = plane.run()
    assert rep.summary["finished"] == 4.0
    assert sum(rep.routed.values()) == 4
    chains_ok = plane.merged_trace() is not None
    assert chains_ok
    if registry is not None:
        plane.publish(registry)
    devs = len(jax.devices())
    return [
        ("cluster.plane.replicas", rep.summary["replicas"], "engines"),
        ("cluster.plane.throughput_tok_s",
         rep.summary["throughput_tok_s"], "tok/s (real smoke decode)"),
        ("cluster.plane.devices", float(devs),
         "jax devices backing the replica meshes"),
    ]


def run(smoke: bool = False,
        registry=None) -> List[Tuple[str, float, str]]:
    n_sessions = 16 if smoke else 60
    sessions = synth_sessions(n_sessions)
    tb = multi_host_pod(N_HOSTS)
    rows: List[Tuple[str, float, str]] = []

    results: Dict[str, RoutingResult] = {}
    for policy in POLICIES:
        r = simulate_routing(policy, sessions, testbed=tb)
        results[policy] = r
        rows.append((f"cluster.{r.policy}.agg_tok_s", r.agg_tok_s,
                     "tok/s"))
        rows.append((f"cluster.{r.policy}.victim_p95_s",
                     r.victim_p95_s, "s (worst-session completion)"))
        rows.append((f"cluster.{r.policy}.spilled_GiB",
                     r.spilled_bytes / GiB, "GiB beyond fast tiers"))

    hd = results["headroom-distance"]
    rr = results["round-robin"]
    rnd = results["random"]
    speedup = hd.agg_tok_s / max(rr.agg_tok_s, 1e-12)
    rows.append(("cluster.routing_speedup", speedup,
                 "x (headroom-distance / round-robin agg tok/s)"))
    rows.append(("cluster.routing_speedup_vs_random",
                 hd.agg_tok_s / max(rnd.agg_tok_s, 1e-12), "x"))
    rows.append(("cluster.victim_p95_improvement",
                 rr.victim_p95_s / max(hd.victim_p95_s, 1e-12),
                 "x (round-robin p95 / headroom-distance p95)"))

    # acceptance: topology-aware routing beats both capacity-blind
    # baselines on aggregate throughput, and never at the victims'
    # expense
    assert speedup >= 1.1, (
        f"headroom-distance routing at {speedup:.2f}x of round-robin "
        f"(want >= 1.1x): the capacity signal is not being used")
    assert hd.agg_tok_s >= rnd.agg_tok_s, (
        "headroom-distance routing lost to random placement")
    assert hd.victim_p95_s <= rr.victim_p95_s * 1.0001, (
        f"victim p95 regressed: {hd.victim_p95_s:.3f}s vs round-robin "
        f"{rr.victim_p95_s:.3f}s")
    assert hd.spilled_bytes <= rr.spilled_bytes, (
        "headroom-aware routing spilled more than round-robin")

    granted = check_plane_arbiter(sessions)
    rows.append(("cluster.arbiter.granted_tenants", float(granted),
                 "per-replica grants under physical caps"))

    rows.extend(run_plane_smoke(registry=registry))
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for key, val, unit in run(smoke=args.smoke):
        print(f"{key},{val:.6g},{unit}")


if __name__ == "__main__":
    main()
