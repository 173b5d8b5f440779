"""TieredStateStore: ledger-registered pytrees with a re-place executor.

The missing piece between ``launch/train.py --adaptive`` and reality:
the replanner used to *plan* moves of fp32 optimizer state and stop
there.  The store holds named pytrees (e.g. ``opt_state_fp32``) as
block-granular ``TieredArray``s whose per-block *tier labels* live here
(a tier name like HOST or CXL maps to a JAX memory kind only at
``device_put`` time, so two tiers that share a memory kind, such as
HOST and RDRAM, stay distinct), and exposes ``move_fn`` — the
``MigrationExecutor`` hook that realizes an object-level byte move as
real block re-placements, gated by the ledger's budgets and recorded
there (the store is the physical client, so it does the recording).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax

from ..core.tiered_array import (sharding_for_kind, TIER_TO_MEMORY_KIND,
                                 TieredArray)
from .ledger import ResidencyLedger

Share = Tuple[str, float]


@dataclasses.dataclass
class _Leaf:
    """One pytree leaf: the placed array + per-block tier labels."""

    ta: TieredArray
    labels: List[str]       # tier name of each block (kinds may collide)


class TieredStateStore:
    """Named pytrees placed across tiers, moved through the ledger."""

    def __init__(self, ledger: ResidencyLedger, tenant: str,
                 tier_to_kind: Optional[Mapping[str, str]] = None,
                 block_rows: Optional[int] = None):
        self.ledger = ledger
        self.tenant = tenant
        ledger.register_tenant(tenant)
        self.tier_to_kind = dict(tier_to_kind or TIER_TO_MEMORY_KIND)
        self.block_rows = block_rows
        self._objs: Dict[str, List[_Leaf]] = {}
        self._treedefs: Dict[str, object] = {}

    def _kind(self, tier: str) -> str:
        return self.tier_to_kind.get(tier, "device")

    # ------------------------------------------------------------------ #
    def put(self, name: str, tree, shares: Sequence[Share]) -> None:
        """Place every leaf of ``tree`` under ``name`` with tier-name
        ``shares`` and register the residency with the ledger."""
        if name in self._objs:
            self.drop(name)
        import jax.numpy as jnp
        flat, treedef = jax.tree.flatten(tree)
        leaves: List[_Leaf] = []
        placement: Dict[str, int] = {}
        for x in flat:
            x = jnp.asarray(x)
            if x.ndim == 0:
                x = x[None]
            spans = TieredArray.plan_blocks(x.shape[0], shares,
                                            self.block_rows)
            blocks, kinds, labels = [], [], []
            per_row = x.nbytes // max(x.shape[0], 1)
            for a, b, tier in spans:
                kind = self._kind(tier)
                blocks.append(jax.device_put(x[a:b],
                                             sharding_for_kind(kind)))
                kinds.append(kind)
                labels.append(tier)
                placement[tier] = placement.get(tier, 0) \
                    + (b - a) * per_row
            leaves.append(_Leaf(TieredArray(blocks, kinds,
                                            tuple(x.shape), x.dtype),
                                labels))
        self._objs[name] = leaves
        self._treedefs[name] = treedef
        if self.ledger.has(self.tenant, name):
            self.ledger.retire(self.tenant, name)
        self.ledger.register(self.tenant, name, placement)

    def drop(self, name: str) -> None:
        self._objs.pop(name, None)
        self._treedefs.pop(name, None)
        self.ledger.retire(self.tenant, name)

    # ------------------------------------------------------------------ #
    def gather(self, name: str):
        """Materialize the object's pytree on device."""
        leaves = [lf.ta.gather() for lf in self._objs[name]]
        return jax.tree.unflatten(self._treedefs[name], leaves)

    def update(self, name: str, tree) -> None:
        """Write fresh values back, preserving block placement — the
        mid-run refresh that keeps a migration moving *current* bytes."""
        flat, _ = jax.tree.flatten(tree)
        leaves = self._objs[name]
        if len(flat) != len(leaves):
            raise ValueError(f"{name}: tree shape changed")
        for lf, x in zip(leaves, flat):
            import jax.numpy as jnp
            x = jnp.asarray(x)
            if x.ndim == 0:
                x = x[None]
            lf.ta = lf.ta.update(x)

    def nbytes(self, name: str) -> int:
        return sum(lf.ta.nbytes for lf in self._objs.get(name, ()))

    def bytes_on(self, name: str, tier: str) -> int:
        """Tier occupancy, read through the ledger (single source)."""
        return self.ledger.object_bytes(self.tenant, name, tier)

    def shares(self, name: str) -> List[Share]:
        total = self.nbytes(name)
        place = self.ledger.placement(self.tenant, name)
        return [(t, b / max(total, 1)) for t, b in sorted(place.items())]

    # ------------------------------------------------------------------ #
    def demote_over_budget(self, fast_tier: str, slow_tier: str) -> int:
        """Ledger-driven compliance for training state: when an arbiter
        shrank this tenant's ``fast_tier`` budget below its holdings,
        demote blocks to ``slow_tier`` until the ledger reconciles —
        the state-store mirror of the scheduler's budget preemption
        (which evicts sequences; state has no queue to re-enter, so it
        demotes in place).  Returns the bytes demoted."""
        moved = 0
        for name in sorted(self._objs):
            over = self.ledger.over_budget(self.tenant, fast_tier)
            if over <= 0:
                break
            moved += self.move_fn(name, fast_tier, slow_tier, over)
        return moved

    def move_fn(self, obj: str, src: str, dst: str, nbytes: int) -> int:
        """MigrationExecutor hook: realize an object-level byte move as
        block re-placements.  Budget-gated per block through the ledger;
        returns the bytes actually moved."""
        leaves = self._objs.get(obj)
        if leaves is None or src == dst:
            return 0
        dst_kind = self._kind(dst)
        moved = 0
        for lf in leaves:
            per_row = lf.ta.nbytes // max(lf.ta.shape[0], 1)
            for i, label in enumerate(lf.labels):
                if moved >= nbytes:
                    break
                if label != src:
                    continue
                blk_bytes = lf.ta.blocks[i].shape[0] * per_row
                if moved and moved + blk_bytes > nbytes:
                    break      # next whole block would overshoot the
                    #            request (a sub-block request may still
                    #            round up to its single first block)
                if not self.ledger.can_place(self.tenant, dst, blk_bytes):
                    break
                lf.ta.move_block(i, dst_kind)
                lf.labels[i] = dst
                self.ledger.record_move(self.tenant, obj, src, dst,
                                        blk_bytes)
                moved += blk_bytes
        return moved
