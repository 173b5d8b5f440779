"""Cluster plane: namespaced ledgers, session router, hierarchical
arbiter split, per-link interference overrides, and the ClusterPlane
end-to-end on the single test device."""
import jax
import numpy as np
import pytest

from repro.cluster import (DEFAULT_REPLICA, ClusterPlane, Namespace,
                           SessionRequest, SessionRouter, is_pattern,
                           replica_meshes, reset_bare_key_warning)
from repro.configs import get_smoke_config
from repro.models import lm
from repro.obs import qos_chains
from repro.pool import ResidencyLedger, TierBudgetArbiter
from repro.serving import (ClusterOptions, ConfigError, ServingConfig,
                           TieringOptions)
from repro.serving.config import validate_args
from repro.topology import TopologyGraph, multi_host_pod

MiB = 2**20


# ===================================================================== #
# Namespace: round-trip, short form, globs                              #
# ===================================================================== #
def test_namespace_roundtrip_all_forms():
    for s in ("a", "host0/serving", "host1/t/kv"):
        ns = Namespace.parse(s)
        assert Namespace.parse(str(ns)) == ns
        assert str(ns) == s
    # canonical long form always carries the replica
    assert Namespace.parse("a").key == "default/a"
    assert Namespace.parse("host0/serving/kv").key == "host0/serving/kv"


def test_namespace_short_form_preserves_legacy_keys():
    # the API-compat contract: pre-cluster tenant names render unchanged
    assert str(Namespace(tenant="serving")) == "serving"
    assert str(Namespace(replica=DEFAULT_REPLICA, tenant="a")) == "a"
    assert str(Namespace(replica="host1", tenant="a")) == "host1/a"


def test_namespace_component_validation():
    with pytest.raises(ValueError):
        Namespace(tenant="a/b")
    with pytest.raises(ValueError):
        Namespace.parse("a/b/c/d")
    with pytest.raises(ValueError):
        Namespace(tenant="a").matches("a/b/c/d")


def test_namespace_glob_matching():
    ns = Namespace(replica="host1", tenant="serving", obj="kv3")
    assert ns.matches("host1/*")
    assert ns.matches("*/serving")
    assert ns.matches("host?/serving/kv*")
    assert not ns.matches("host0/*")
    # bare pattern addresses the default replica, mirroring of()
    assert not ns.matches("serving")
    assert Namespace(tenant="serving").matches("serving")
    assert is_pattern("host*/x") and not is_pattern("host0/x")


def test_namespace_ordering_groups_replicas():
    keys = [Namespace(replica="h1", tenant="b"),
            Namespace(replica="h0", tenant="z"),
            Namespace(replica="h0", tenant="a")]
    ordered = [str(ns) for ns in sorted(keys)]
    assert ordered == ["h0/a", "h0/z", "h1/b"]


def test_namespace_derivation_helpers():
    ns = Namespace.parse("h0/t")
    assert ns.with_obj("kv").obj == "kv"
    assert ns.with_obj("kv").tenant_key() == ns
    assert ns.in_replica("h1").key == "h1/t"


# ===================================================================== #
# Bare-string shim: warn once per process                               #
# ===================================================================== #
def test_bare_key_shim_warns_once():
    reset_bare_key_warning()
    with pytest.warns(DeprecationWarning, match="bare tenant key"):
        assert Namespace.of("legacy") == Namespace(tenant="legacy")
    # second bare key is silent — once per process, not per call
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert Namespace.of("other").key == "default/other"
        # namespaced keys and glob patterns never warn
        reset_bare_key_warning()
        assert Namespace.of("h0/t").replica == "h0"
        assert Namespace.of(Namespace(tenant="x")).tenant == "x"
        assert Namespace.of("*").tenant == "*"
    reset_bare_key_warning()


# ===================================================================== #
# Ledger: per-replica namespaces sum exactly to the global view         #
# ===================================================================== #
def _two_replica_ledger():
    led = ResidencyLedger()
    for t in ("h0/serving", "h1/serving", "h1/batch"):
        led.register_tenant(t)
    led.register("h0/serving", "kv0", {"FAST": 4 * MiB, "CXL": 1 * MiB})
    led.register("h1/serving", "kv1", {"FAST": 2 * MiB})
    led.register("h1/batch", "kv2", {"FAST": 3 * MiB, "CXL": 5 * MiB})
    return led


def test_ledger_namespace_aggregation_is_conserved():
    led = _two_replica_ledger()
    total = led.aggregate("*/*")
    by_host = [led.aggregate("h0/*"), led.aggregate("h1/*")]
    for tier in total:
        assert total[tier] == sum(a.get(tier, 0) for a in by_host)
    assert led.bytes_on("FAST", "h0/*") == 4 * MiB
    assert led.bytes_on("FAST", "h1/*") == 5 * MiB
    assert led.bytes_on("FAST", "*/*") == 9 * MiB
    assert led.bytes_on("CXL", "*/serving") == 1 * MiB


def test_ledger_accepts_namespace_and_legacy_keys():
    led = ResidencyLedger()
    led.register_tenant(Namespace(replica="h0", tenant="t"))
    led.register(Namespace.parse("h0/t"), "kv", {"FAST": MiB})
    assert led.tenant_bytes("h0/t") == MiB
    # a pre-cluster bare key lands in the default replica
    reset_bare_key_warning()
    with pytest.warns(DeprecationWarning):
        led.register_tenant("old")
    led.register("default/old", "kv", {"FAST": MiB})
    assert led.bytes_on("FAST", "default/*") == MiB
    assert led.bytes_on("FAST", "*/*") == 2 * MiB
    reset_bare_key_warning()


# ===================================================================== #
# SessionRouter: policies, degenerate cases, pending reservations       #
# ===================================================================== #
def _req(sid, kv=None):
    return SessionRequest(session_id=sid, prompt_tokens=8, new_tokens=8,
                          kv_bytes_hint=kv)


def test_router_rejects_unknown_policy_and_empty_registry():
    with pytest.raises(ConfigError, match="unknown router policy"):
        SessionRouter("best-effort")
    r = SessionRouter("round-robin")
    with pytest.raises(ConfigError, match="no registered replicas"):
        r.route(_req("s0"))


def test_router_single_replica_fast_path():
    r = SessionRouter("headroom-distance")
    r.register("only", distance_ns=5.0, headroom_fn=lambda: 0)
    assert [r.route(_req(f"s{i}")) for i in range(3)] == ["only"] * 3
    assert r.routed_counts() == {"only": 3}


def test_router_zero_headroom_degrades_to_least_loaded():
    r = SessionRouter("headroom-distance")
    load = {"near": 4, "far": 1}
    for name, d in (("near", 1.0), ("far", 9.0)):
        r.register(name, distance_ns=d, headroom_fn=lambda: 0,
                   load_fn=lambda n=name: load[n])
    # both full: the lighter replica wins despite being farther
    assert r.route(_req("s0", kv=MiB)) == "far"


def test_router_headroom_dominates_distance():
    r = SessionRouter("headroom-distance")
    r.register("near", distance_ns=1.0, headroom_fn=lambda: 2 * MiB)
    r.register("far", distance_ns=9.0, headroom_fn=lambda: 10 * MiB)
    # only far can hold the whole session fast
    assert r.route(_req("s0", kv=4 * MiB)) == "far"
    # comparable headroom: distance breaks the tie
    r2 = SessionRouter("headroom-distance")
    r2.register("far", distance_ns=9.0, headroom_fn=lambda: 8 * MiB)
    r2.register("near", distance_ns=1.0, headroom_fn=lambda: 8 * MiB)
    assert r2.route(_req("s1", kv=MiB)) == "near"


def test_router_pending_reservations_spread_batches():
    """Without live pool feedback, in-flight kv reservations must keep
    a batch of identical submissions off a single replica."""
    r = SessionRouter("headroom-distance")
    for name in ("a", "b"):
        r.register(name, distance_ns=1.0, headroom_fn=lambda: 8 * MiB)
    picks = [r.route(_req(f"s{i}", kv=3 * MiB)) for i in range(4)]
    assert set(picks) == {"a", "b"}
    assert picks.count("a") == picks.count("b") == 2
    r.drain_pending()
    assert all(v.pending_bytes == 0 for v in r._views.values())


def test_router_baseline_policies():
    rr = SessionRouter("round-robin")
    rnd = SessionRouter("random", seed=7)
    ll = SessionRouter("least-loaded")
    load = {"a": 3, "b": 0}
    for router in (rr, rnd, ll):
        for name in ("a", "b"):
            router.register(name, distance_ns=1.0,
                            load_fn=lambda n=name: load[n])
    assert [rr.route(_req(f"s{i}")) for i in range(4)] == \
        ["a", "b", "a", "b"]
    assert set(rnd.route(_req(f"s{i}")) for i in range(8)) == {"a", "b"}
    assert ll.route(_req("s0")) == "b"


# ===================================================================== #
# Hierarchical arbiter: replica groups first, tenants within            #
# ===================================================================== #
def test_arbiter_split_respects_replica_capacity():
    led = _two_replica_ledger()
    cap = {"h0": 2 * MiB, "h1": 3 * MiB}
    arb = TierBudgetArbiter(led, "FAST",
                            capacity_bytes=sum(cap.values()),
                            replica_capacity=cap)
    grant = arb.split(arb.demands())
    # no trace attached -> whole residency is demand; h0/serving wants
    # 5 MiB but its host only has 2 MiB of physical fast tier
    by_replica = {}
    for tenant, g in grant.items():
        by_replica.setdefault(Namespace.of(tenant).replica, 0)
        by_replica[Namespace.of(tenant).replica] += g
    assert by_replica["h0"] <= cap["h0"]
    assert by_replica["h1"] <= cap["h1"]
    assert by_replica["h0"] == 2 * MiB          # capped, not starved
    assert by_replica["h1"] == 3 * MiB


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_arbiter_single_replica_degenerates_to_flat_split():
    led = ResidencyLedger()
    for t in ("a", "b"):
        led.register_tenant(t)
        led.register(t, "kv", {"FAST": 4 * MiB})
    flat = TierBudgetArbiter(led, "FAST", capacity_bytes=4 * MiB)
    grouped = TierBudgetArbiter(led, "FAST", capacity_bytes=4 * MiB,
                                replica_capacity={"default": 4 * MiB})
    assert flat.split(flat.demands()) == grouped.split(grouped.demands())


# ===================================================================== #
# InterferenceMatrix.with_link_scales: one physical link, not its kind  #
# ===================================================================== #
def _two_cxl_link_graph():
    g = TopologyGraph("two-cxl")
    g.add_node("s0")
    g.add_node("cxl0", kind="cxl")
    g.add_node("cxl1", kind="cxl")
    g.add_link("s0", "cxl0", 150.0, 38.4, kind="cxl")
    g.add_link("s0", "cxl1", 150.0, 38.4, kind="cxl")
    return g


def test_link_scales_override_one_link_only():
    g = _two_cxl_link_graph()
    m = g.interference.with_link_scales("s0-cxl0",
                                        {("read", "write"): 2.0})
    base = g.interference.weight("cxl", "read", "write")
    hot = m.weight("cxl", "read", "write", link=("s0", "cxl0"))
    cold = m.weight("cxl", "read", "write", link=("s0", "cxl1"))
    assert hot == pytest.approx(2.0 * base)
    assert cold == pytest.approx(base)          # same kind, other link
    # link order is normalized: (b, a) prices like (a, b)
    assert m.weight("cxl", "read", "write",
                    link=("cxl0", "s0")) == pytest.approx(hot)


def test_link_scales_take_precedence_over_pair_scales():
    m = TopologyGraph("g").interference \
        .with_pair_scales({("cxl", "read", "write"): 3.0}) \
        .with_link_scales(("s0", "cxl0"), {("read", "write"): 1.5})
    kind_level = m.weight("cxl", "read", "write")
    link_level = m.weight("cxl", "read", "write", link=("s0", "cxl0"))
    base = TopologyGraph("g").interference.weight("cxl", "read", "write")
    assert kind_level == pytest.approx(3.0 * base)
    assert link_level == pytest.approx(1.5 * base)   # link wins
    with pytest.raises(ValueError, match="not 'a-b'"):
        m.with_link_scales("nodash", {("read", "write"): 2.0})


def test_link_scales_survive_graph_rebuilt():
    g = _two_cxl_link_graph()
    g.interference = g.interference.with_link_scales(
        "s0-cxl0", {("read", "write"): 2.0})
    g2 = g.rebuilt(link_overrides={(("cxl1", "s0")): (150.0, 20.0)})
    before = g.interference.weight("cxl", "read", "write",
                                   link=("s0", "cxl0"))
    after = g2.interference.weight("cxl", "read", "write",
                                   link=("s0", "cxl0"))
    assert after == pytest.approx(before)


# ===================================================================== #
# ClusterPlane end-to-end (each replica on its own CPU test device)    #
# ===================================================================== #
@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("llama3-8b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _plane(cfg, params, **kw):
    kw.setdefault("serving", ServingConfig(
        block_tokens=8, max_batch=2, max_context=32, policy="tiering08"))
    return ClusterPlane(cfg, params, n_replicas=2, **kw)


def test_replica_meshes_cover_all_devices():
    meshes = replica_meshes(2)
    assert len(meshes) == 2
    devs = [tuple(d.id for d in m.devices.flat) for m in meshes]
    assert not set(devs[0]) & set(devs[1])
    assert sum(map(len, devs)) == len(jax.devices())


def test_replica_meshes_refuse_shared_devices():
    devs = jax.devices()
    with pytest.raises(ValueError, match="need 3 devices"):
        replica_meshes(3, devices=devs[:2])
    with pytest.raises(ValueError, match="listed twice"):
        replica_meshes(2, devices=[devs[0], devs[0]])


def test_replica_refuses_arrays_off_its_mesh(tiny):
    cfg, params = tiny
    plane = _plane(cfg, params)
    rep = plane.replicas["host1"]
    assert set(rep.mesh.devices.flat).isdisjoint({jax.devices()[0]})
    rep.check_placement()
    # a block array made with no device lands on chip 0
    blk = rep.engine.pool.blocks[0]
    blk.kv = jax.numpy.zeros(rep.engine.pool.spec.payload_shape)
    with pytest.raises(RuntimeError, match="outside its mesh"):
        rep.check_placement()


def test_plane_routes_runs_and_conserves_namespaces(tiny):
    cfg, params = tiny
    plane = _plane(cfg, params)
    rs = np.random.RandomState(0)
    rids = [plane.submit(rs.randint(0, cfg.vocab, (8,)).astype(np.int32),
                         4, arrival_s=0.005 * i) for i in range(4)]
    # submissions spread across replicas via pending reservations
    assert set(r.split(":")[0] for r in rids) == set(plane.replicas)
    rep = plane.run()
    assert rep.summary["finished"] == 4.0
    assert rep.summary["replicas"] == 2.0
    assert sum(rep.routed.values()) == 4
    assert rep.aggregate_throughput() > 0
    # the acceptance invariant: per-replica ledger bytes sum exactly
    # to the global aggregate, across every tier in play
    for tier in plane.ledger.aggregate("*/*"):
        per = {h: plane.ledger.bytes_on(tier, f"{h}/*")
               for h in plane.replicas}
        assert sum(per.values()) == plane.ledger.bytes_on(tier, "*/*")
    cons = plane.namespace_conservation()
    assert sum(v for h, v in cons.items() if h != "total") == \
        cons["total"]


def test_plane_replica_tenants_are_namespaced(tiny):
    cfg, params = tiny
    plane = _plane(cfg, params)
    names = {str(rep.ns) for rep in plane.replicas.values()}
    assert names == {"host0/serving", "host1/serving"}
    # each replica engine registered its pool under its namespace in
    # the one shared ledger
    tenants = {str(ns) for ns in plane.ledger.tenants}
    assert names <= tenants


def test_plane_publish_exports_per_replica_gauges(tiny):
    cfg, params = tiny
    plane = _plane(cfg, params)
    n = plane.publish()
    assert n > 0
    names = plane.registry.names()
    for host in plane.replicas:
        for g in ("fast_headroom_bytes", "active_sessions",
                  "routed_sessions", "distance_ns"):
            assert f"cluster.{host}.{g}" in names
    # host0 sits next to the front-end; host1 pays the ICI hop
    d0 = plane.registry.gauge("cluster.host0.distance_ns").value
    d1 = plane.registry.gauge("cluster.host1.distance_ns").value
    assert d0 < d1


def test_merged_trace_keeps_per_replica_qos_chains(tiny):
    """qos_chains pairs a violation with the blame event that follows
    it in sequence, so the merge must keep each replica's event order
    intact rather than interleaving by timestamp."""
    cfg, params = tiny
    plane = _plane(cfg, params)
    for i, (host, rep) in enumerate(plane.replicas.items()):
        tr = rep.engine.tracer
        tr.event("slo.violation", cat="slo", tid="serving",
                 metric="decode_latency", host=host)
        tr.event("qos.blame", cat="qos", tid="serving",
                 antagonist=f"noisy{i}", link="ici", host=host)
    chains = qos_chains(plane.merged_trace())
    assert len(chains) == 2
    for c in chains:
        assert c["blame"] is not None
        # blame joined to its own replica's violation, never a sibling's
        assert c["blame"].args["host"] == c["violation"].args["host"]
        assert c["blame"].tid.split("/")[0] == \
            c["violation"].tid.split("/")[0]


def test_plane_rejects_undersized_testbed(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="hosts for"):
        ClusterPlane(cfg, params, n_replicas=4,
                     testbed=multi_host_pod(2))


def test_plane_arbiter_splits_under_physical_caps(tiny):
    cfg, params = tiny
    plane = _plane(cfg, params)
    grant = plane.arbiter.split(plane.arbiter.demands())
    per_replica = {}
    for tenant, g in grant.items():
        r = Namespace.of(tenant).replica
        per_replica[r] = per_replica.get(r, 0) + g
    for host, cap in plane.replica_fast_bytes.items():
        assert per_replica.get(host, 0) <= cap


# ===================================================================== #
# Config sections: two-way sync, from_args, centralized validation      #
# ===================================================================== #
def test_config_flat_kwargs_populate_sections():
    sc = ServingConfig(adaptive=True, expert_policy="lru", qos=False)
    assert sc.tiering.adaptive is True
    assert sc.experts.policy == "lru"
    assert sc.qos_options.enabled is False
    assert sc.cluster is None                  # no legacy flat kwargs


def test_config_section_wins_over_flat_kwargs():
    sc = ServingConfig(policy="tiering08",
                       tiering=TieringOptions(policy="static",
                                              num_blocks=7))
    assert sc.policy == "static"               # section overwrote flat
    assert sc.num_blocks == 7


def test_cluster_options_validate_eagerly():
    with pytest.raises(ConfigError, match="replicas must be >= 1"):
        ClusterOptions(replicas=0)
    with pytest.raises(ConfigError, match="unknown router policy"):
        ClusterOptions(router="fastest")


def _args(**kw):
    import argparse
    ns = argparse.Namespace(scheduler="continuous", tenant=None)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_from_args_builds_cluster_options():
    sc = ServingConfig.from_args(_args(replicas=2, router="round-robin"))
    assert sc.cluster is not None
    assert sc.cluster.replicas == 2
    assert sc.cluster.router == "round-robin"
    assert ServingConfig.from_args(_args()).cluster is None


def test_validate_args_cross_field_rules():
    with pytest.raises(ConfigError, match="--predictive requires"):
        validate_args(_args(predictive=True))
    with pytest.raises(ConfigError, match="requires --adaptive"):
        validate_args(_args(calibrate=True))
    with pytest.raises(ConfigError, match="--scheduler continuous"):
        validate_args(_args(scheduler="static", replicas=2))
    with pytest.raises(ConfigError, match="not yet supported"):
        validate_args(_args(replicas=2, fused_gather=True))
    with pytest.raises(ConfigError, match="not yet supported"):
        validate_args(_args(replicas=2, expert_policy="lru"))
    with pytest.raises(ConfigError, match="unknown --router"):
        validate_args(_args(router="fastest"))
    # the happy paths raise nothing
    validate_args(_args(replicas=2, router="headroom-distance"))
    validate_args(_args(adaptive=True, predictive=True, calibrate=True))
