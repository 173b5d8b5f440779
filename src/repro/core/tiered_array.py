"""TieredArray: block-granular array placement across JAX memory kinds.

This realizes the paper's page-interleaving mechanics with the TPU-native
mechanism: an array is split into blocks along its leading axis and each
block is placed in a memory kind ("device" = HBM/fast tier,
"pinned_host"/"unpinned_host" = the CXL-analogue capacity tiers).

API:
  ta = TieredArray.place(x, shares=[("device", .5), ("pinned_host", .5)])
  y  = ta.gather()                # materialize on device (blocking)
  it = ta.prefetch_blocks()       # double-buffered async block stream
  ta2 = ta.update(new_x)          # write back preserving placement

`gather` issues all device transfers up front (jax.device_put is
asynchronous) so host->device DMA of later blocks overlaps the concat of
earlier ones — the block-granular analogue of the paper's "distribute
memory accesses between tiers" guidance.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Share = Tuple[str, float]  # (memory kind, fraction)

# Map tier names (core.tiers) to JAX memory kinds on the accelerator host.
TIER_TO_MEMORY_KIND = {
    "HBM": "device",
    "LDRAM": "device",          # in paper-system replays the fast tier
    "HOST": "pinned_host",
    "RDRAM": "pinned_host",
    "CXL": "unpinned_host",
    "ICI_PEER": "device",
    "HOST_UNPINNED": "unpinned_host",
    "NVMe": "unpinned_host",
}


def available_memory_kinds(device: Optional[jax.Device] = None
                           ) -> List[str]:
    """Memory kinds ``device`` (default: the first device) exposes."""
    device = device or jax.devices()[0]
    return sorted(m.kind for m in device.addressable_memories())


def sharding_for_kind(memory_kind: str,
                      device: Optional[jax.Device] = None):
    """SingleDeviceSharding on ``memory_kind``.  Raises when the device
    has no such memory: a placement that silently landed in default
    memory would hide a wrong tier."""
    device = device or jax.devices()[0]
    kinds = available_memory_kinds(device)
    if memory_kind not in kinds:
        raise ValueError(f"{device} has no memory kind {memory_kind!r} "
                         f"(it has {kinds})")
    return jax.sharding.SingleDeviceSharding(device, memory_kind=memory_kind)


@dataclasses.dataclass
class TieredArray:
    """An array split into per-memory-kind blocks along axis 0."""

    blocks: List[jax.Array]       # in order, concat along axis 0 == array
    kinds: List[str]              # memory kind of each block
    shape: Tuple[int, ...]
    dtype: jnp.dtype

    # ------------------------------------------------------------------ #
    @staticmethod
    def plan_blocks(n_rows: int, shares: Sequence[Share],
                    block_rows: Optional[int] = None
                    ) -> List[Tuple[int, int, str]]:
        """Compute (start, stop, kind) block spans for the share list.

        With `block_rows` set, shares are realized round-robin at block
        granularity (true interleaving); otherwise each share is one
        contiguous span (numactl membind-style).
        """
        shares = [(k, f) for k, f in shares if f > 0]
        if not shares:
            raise ValueError("empty share list")
        total_f = sum(f for _, f in shares)
        shares = [(k, f / total_f) for k, f in shares]
        if block_rows is None:
            spans = []
            start = 0
            for i, (k, f) in enumerate(shares):
                stop = n_rows if i == len(shares) - 1 else min(
                    n_rows, start + max(1, int(round(f * n_rows))))
                if stop > start:
                    spans.append((start, stop, k))
                start = stop
            return spans
        # round-robin interleave at block_rows granularity, weighted by f
        n_blocks = math.ceil(n_rows / block_rows)
        seq: List[str] = []
        counts = {k: 0.0 for k, _ in shares}
        for _ in range(n_blocks):
            # pick kind with largest deficit vs target fraction
            k = max(shares, key=lambda kf: kf[1] * (len(seq) + 1)
                    - counts[kf[0]])[0]
            seq.append(k)
            counts[k] += 1.0
        spans = []
        for i, k in enumerate(seq):
            a, b = i * block_rows, min((i + 1) * block_rows, n_rows)
            spans.append((a, b, k))
        return spans

    @classmethod
    def place(cls, x: jax.Array, shares: Sequence[Share],
              block_rows: Optional[int] = None) -> "TieredArray":
        x = jnp.asarray(x)
        if x.ndim == 0:
            x = x[None]
        spans = cls.plan_blocks(x.shape[0], shares, block_rows)
        blocks, kinds = [], []
        for a, b, kind in spans:
            blk = jax.device_put(x[a:b], sharding_for_kind(kind))
            blocks.append(blk)
            kinds.append(kind)
        return cls(blocks, kinds, tuple(x.shape), x.dtype)

    @classmethod
    def from_plan(cls, x: jax.Array, tier_shares: Sequence[Tuple[str, float]],
                  block_rows: Optional[int] = None) -> "TieredArray":
        """Place using core.tiers tier *names* (mapped to memory kinds)."""
        shares = [(TIER_TO_MEMORY_KIND.get(t, "device"), f)
                  for t, f in tier_shares]
        # merge duplicate kinds
        merged: Dict[str, float] = {}
        for k, f in shares:
            merged[k] = merged.get(k, 0.0) + f
        return cls.place(x, list(merged.items()), block_rows)

    # ------------------------------------------------------------------ #
    def gather(self) -> jax.Array:
        """Materialize the full array in device memory.

        All block transfers are dispatched first (async), then concatenated:
        later DMAs overlap earlier concat work.
        """
        dev = sharding_for_kind("device")
        moved = [jax.device_put(b, dev) for b in self.blocks]  # async batch
        if len(moved) == 1:
            return moved[0].reshape(self.shape)
        return jnp.concatenate(moved, axis=0).reshape(self.shape)

    def prefetch_blocks(self) -> Iterator[jax.Array]:
        """Double-buffered block stream: block i+1's DMA is in flight while
        block i is consumed (the ZeRO-Offload bucket pipeline)."""
        dev = sharding_for_kind("device")
        nxt = jax.device_put(self.blocks[0], dev)
        for i in range(len(self.blocks)):
            cur = nxt
            if i + 1 < len(self.blocks):
                nxt = jax.device_put(self.blocks[i + 1], dev)
            yield cur

    def move_block(self, i: int, kind: str) -> int:
        """Re-place block ``i`` onto ``kind`` in place (a real
        jax.device_put between memory kinds); returns the bytes moved
        (0 when the block already lives there)."""
        if self.kinds[i] == kind:
            return 0
        self.blocks[i] = jax.device_put(self.blocks[i],
                                        sharding_for_kind(kind))
        self.kinds[i] = kind
        per_row = self.nbytes // max(self.shape[0], 1)
        return self.blocks[i].shape[0] * per_row

    def update(self, x: jax.Array) -> "TieredArray":
        """Write a new value back, preserving the block placement."""
        x = jnp.asarray(x, dtype=self.dtype).reshape(self.shape)
        out_blocks = []
        start = 0
        for b, kind in zip(self.blocks, self.kinds):
            stop = start + b.shape[0]
            out_blocks.append(
                jax.device_put(x[start:stop], sharding_for_kind(kind)))
            start = stop
        return TieredArray(out_blocks, list(self.kinds), self.shape,
                           self.dtype)

    # ------------------------------------------------------------------ #
    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * jnp.dtype(self.dtype).itemsize

    def bytes_on(self, kind: str) -> int:
        per_row = self.nbytes // max(self.shape[0], 1)
        return sum(b.shape[0] * per_row
                   for b, k in zip(self.blocks, self.kinds) if k == kind)

    def fast_fraction(self) -> float:
        return self.bytes_on("device") / max(self.nbytes, 1)


def place_pytree(tree, shares_fn, block_rows: Optional[int] = None):
    """Place every leaf of a pytree: shares_fn(path, leaf) -> share list."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    placed = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        placed.append(TieredArray.place(leaf, shares_fn(name, leaf),
                                        block_rows))
    return jax.tree_util.tree_unflatten(treedef, placed)


def gather_pytree(tree):
    return jax.tree.map(
        lambda t: t.gather() if isinstance(t, TieredArray) else t, tree,
        is_leaf=lambda t: isinstance(t, TieredArray))
