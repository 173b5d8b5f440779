"""One replica of the multi-host serving plane.

A :class:`Replica` owns the full single-host serving stack — sharded
params on its own device mesh, a :class:`~repro.serving.ServingEngine`
whose paged KV pool is *mesh-placed* (so pool blocks and params share
one jit device set), and a local topology testbed its tiering plane
prices promotions against.

The ownership boundary the namespace scheme encodes: everything the
replica allocates registers in the **shared** residency ledger under
``<replica>/<tenant>`` keys, so the cluster arbiter and the blame
plane see per-replica occupancy without the replica knowing it has
siblings.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tiered_array import sharding_for_kind
from ..serving import ServingConfig, ServingEngine
from .namespace import Namespace
from .sharding import current_axis_mapping, shard_lm_params

__all__ = ["Replica"]


def _mesh_pool_sharding(mesh: Mesh) -> Callable[[str], object]:
    """Pool-block placement on the replica mesh: replicated over its
    devices, on the requested memory kind.  Raises on a kind the
    devices lack, as ``sharding_for_kind`` does."""
    dev = mesh.devices.flat[0]

    def fn(kind: str):
        sharding_for_kind(kind, dev)       # raises on a missing kind
        return NamedSharding(mesh, PartitionSpec(), memory_kind=kind)

    return fn


class Replica:
    """A mesh-sharded serving engine registered under its namespace."""

    def __init__(self, name: str, cfg, params,
                 serving: Optional[ServingConfig] = None,
                 mesh: Optional[Mesh] = None, ledger=None,
                 host: Optional[str] = None, testbed=None,
                 shard_model: bool = True,
                 clock: Optional[Callable[[], float]] = None):
        import dataclasses as _dc
        import time as _time
        self.name = name
        self.host = host or name
        self.mesh = mesh
        sv = _dc.replace(serving) if serving is not None \
            else ServingConfig()
        # the one rename that makes multi-replica ledgers work: this
        # engine's tenant becomes "<replica>/<tenant>" in the shared
        # ledger, short-form-printable and glob-aggregatable
        base = Namespace.of(sv.tenant or "serving")
        self.ns = Namespace(replica=name, tenant=base.tenant)
        sv.tenant = str(self.ns)
        if testbed is not None and sv.topology is None:
            # replicas plan over their own local testbed, not a name
            # the engine would rebuild; wired below after construction
            pass
        pool_sharding = None
        if mesh is not None:
            pool_sharding = _mesh_pool_sharding(mesh)
            if shard_model:
                params = shard_lm_params(params, mesh,
                                         current_axis_mapping())
            else:
                params = jax.device_put(
                    params, NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self.engine = ServingEngine(
            cfg, params, serving=sv,
            clock=clock or _time.perf_counter,
            ledger=ledger, pool_sharding=pool_sharding)
        if testbed is not None and self.engine.topo is None:
            # adopt the cluster's per-replica local graph so the
            # migration executor / replanner price over real links
            from ..serving.engine import FAST_KIND
            topo = testbed.graph
            topo.alias_tier(testbed.fast, FAST_KIND)
            topo.alias_tier(testbed.capacity_tier,
                            self.engine.pool.slow_kind)
            self.engine.topo = topo
        self.testbed = testbed
        self.check_placement()

    def check_placement(self) -> None:
        """Raise if any array the replica holds (params, KV blocks,
        pooled stores) lives on a device outside its mesh — on chip 0,
        say, where every uncommitted array lands by default."""
        if self.mesh is None:
            return
        own = set(self.mesh.devices.flat)
        pool = self.engine.pool
        arrays = jax.tree.leaves(self.params) + [
            b.kv for b in pool.blocks if b.kv is not None]
        arrays += [a for a in (pool.k_store, pool.v_store)
                   if a is not None]
        for a in arrays:
            stray = set(a.devices()) - own
            if stray:
                raise RuntimeError(
                    f"replica {self.name}: an array of shape {a.shape} "
                    f"lives on {sorted(map(str, stray))}, outside its "
                    f"mesh {sorted(map(str, own))}")

    # -- the router's live signals ------------------------------------ #
    def fast_headroom_bytes(self) -> int:
        """Unused fast-tier capacity — the router's dominant term."""
        pool = self.engine.pool
        free = max(0, pool.fast_block_budget - pool.fast_used())
        return free * pool.block_nbytes()

    def active_sessions(self) -> int:
        sched = self.engine.sched
        return len(sched.running) + len(sched.waiting)

    # -- serving pass-throughs ---------------------------------------- #
    def submit(self, prompt, max_new_tokens: int,
               arrival_s: float = 0.0, priority: float = 0.0) -> int:
        return self.engine.submit(prompt, max_new_tokens,
                                  arrival_s=arrival_s, priority=priority)

    def run(self, max_iterations: int = 10_000):
        report = self.engine.run(max_iterations=max_iterations)
        self.check_placement()
        return report

    def __repr__(self) -> str:
        nd = self.mesh.devices.size if self.mesh is not None else 0
        return (f"Replica({self.name!r}, ns={str(self.ns)!r}, "
                f"mesh_devices={nd})")
