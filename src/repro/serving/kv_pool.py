"""Paged KV-cache block pool with tier-resident blocks (vLLM-style).

The serving analogue of the paper's Sec. IV-B finding: the KV cache is
the object whose capacity growth pays for CXL-class tiers, and it is
accessed at *block* granularity (decode streams the whole cache, but a
request's blocks go cold the moment the request finishes or is
preempted).  The pool therefore manages fixed-size token blocks:

  * a block holds ``block_tokens`` tokens of K and V for every attention
    layer of the model, in one buffer ``kv`` of shape
    ``(2, U, n_attn, block_tokens, KV, hd)``: K at index 0, V at 1;
  * each block is resident in one JAX memory kind ("device" = HBM
    analogue, "pinned_host"/"unpinned_host" = the CXL-class capacity
    tiers), moved with ``migrate`` — the mechanism tiering.py drives;
  * tier *occupancy* is not private state: every alloc/free/migrate is
    recorded in a ``repro.pool.ResidencyLedger`` under the pool's
    tenant namespace, and ``blocks_on``/``fast_used`` read back through
    it — so several pools (tenants) can share one ledger and one
    arbitrated fast-tier budget (``ledger.can_place`` gates
    promotions, replacing the old private fast-block counter);
  * a block table maps ``seq_id -> [block ids]`` (logical order);
  * per-block access bits (touch count + last-touch step, the page-table
    A-bit analogue) feed the promotion/demotion policies adapted from
    ``core.migration``, while *aggregate* access heat is emitted as
    telemetry events (``attach_telemetry``) — reads on decode, writes on
    prefill/append — so phase detection and the adaptive replanner see
    the same traffic the tiering policies act on.

The pool also runs in *metadata-only* mode (``spec=None``): alloc/free/
migrate bookkeeping without array payloads, which is what the
trace-driven scheduler benchmark and the pure-logic tests use.

Data mode has two layouts:

  * **per-block** (default): each block owns its own payload array,
    ``device_put`` onto the block's memory kind — migration moves the
    payload.  ``gather_seq`` stages a sequence into one contiguous
    buffer (the gather-then-compute path): its host-resident blocks
    cross to the device in one runtime call, its device-resident
    blocks are used as they are.
  * **pooled** (``pooled=True``): payloads live in two persistent
    per-layer stores ``(U, n_attn, num_blocks, bt, KV, hd)`` indexed by
    physical block id.  This is the layout the fused tiered-gather
    kernel computes over *directly* — ``gather_tables`` hands it the
    int32 block-index table instead of a staging copy.  The stores stay
    in device memory, so a block's tier in this layout is the ledger's
    bookkeeping only: no byte moves.

A block's payload crosses between a host memory kind and the device
as one buffer, and each such crossing is counted in ``PoolCounters``:
bytes and blocks put, and the runtime calls that carried them, each
direction.  Each method that moves payloads opens a ``kv.*`` span on
the profiler's clock (``obs.trace.annotate``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.trace import annotate

FAST_KIND = "device"


@dataclasses.dataclass(frozen=True)
class KVBlockSpec:
    """Shape of one pool block (set from the model config)."""

    n_units: int
    n_attn: int          # attention layers per unit
    block_tokens: int
    n_kv: int
    head_dim: int
    dtype: str = "bfloat16"

    @property
    def kv_shape(self) -> Tuple[int, ...]:
        return (self.n_units, self.n_attn, self.block_tokens, self.n_kv,
                self.head_dim)

    @property
    def payload_shape(self) -> Tuple[int, ...]:
        """A block's K and V in one buffer: K at index 0, V at 1."""
        return (2,) + self.kv_shape

    @property
    def nbytes(self) -> int:
        item = jnp.dtype(self.dtype).itemsize
        return int(np.prod(self.payload_shape)) * item


@dataclasses.dataclass
class KVBlock:
    """One physical block: payload + residency + heat."""

    bid: int
    kind: str                      # current memory kind
    seq_id: Optional[int] = None   # owner sequence (None = free)
    logical_idx: int = -1          # position in the owner's block table
    kv: Optional[object] = None    # jax.Array, spec.payload_shape
    touch_count: int = 0
    last_touch_step: int = -(10 ** 9)

    @property
    def free(self) -> bool:
        return self.seq_id is None

    @property
    def k(self) -> Optional[object]:
        """The whole payload ``kv`` (K and V), read-only; ``None`` until
        the block is written.  It never slices out K: on a host memory
        kind a slice would compute on host memory."""
        return self.kv


class PoolExhausted(Exception):
    """No free blocks left — the scheduler must preempt."""


@dataclasses.dataclass
class PoolCounters:
    allocs: int = 0
    frees: int = 0
    promoted: int = 0
    demoted: int = 0
    migrated_bytes: int = 0
    defrags: int = 0
    # payload transfers between a host memory kind and the device
    # (PCIe on a TPU host): bytes, blocks put (one buffer a block), and
    # the runtime calls that moved at least one block
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    h2d_puts: int = 0
    d2h_puts: int = 0
    h2d_calls: int = 0
    d2h_calls: int = 0


def _stack_padded(kv_k, kv_v, pad):
    """A prefill's K and V, (U, n_attn, n, KV, hd) each, as one
    (2, U, n_attn, n + pad, KV, hd) buffer zero-padded on the token
    axis."""
    kv = jnp.stack([kv_k, kv_v])
    return jnp.pad(kv, [(0, 0)] * 3 + [(0, pad)] + [(0, 0)] * 2)


def _concat_blocks(parts):
    """One sequence's block payloads in logical order, joined along the
    token axis: (2, U, n_attn, len(parts) * bt, KV, hd)."""
    return jnp.concatenate(parts, axis=3)


def _write_token(kv, k_tok, v_tok, off):
    """``kv`` with one token's K and V, (U, n_attn, KV, hd) each, at
    token offset ``off``; ``kv`` is donated, so the update is in place."""
    tok = jnp.stack([k_tok, v_tok]).astype(kv.dtype)
    return lax.dynamic_update_index_in_dim(kv, tok, off, axis=3)


_stack_padded = jax.jit(_stack_padded, static_argnums=2)
_concat_blocks = jax.jit(_concat_blocks)
_write_token = jax.jit(_write_token, donate_argnums=0)


class PagedKVPool:
    """Fixed-size paged KV pool over tiered memory kinds.

    ``num_blocks`` bounds total KV capacity; ``fast_block_budget`` bounds
    how many blocks may reside on the fast kind at once (the HBM-analogue
    capacity budget from core.tiers / the cost model).
    """

    def __init__(self, num_blocks: int, block_tokens: int,
                 spec: Optional[KVBlockSpec] = None,
                 fast_block_budget: Optional[int] = None,
                 slow_kind: str = "pinned_host",
                 default_kind: Optional[str] = None,
                 ledger=None, tenant: str = "kv",
                 pooled: bool = False, sharding_fn=None):
        if num_blocks <= 0:
            raise ValueError("num_blocks must be positive")
        if block_tokens <= 0:
            raise ValueError("block_tokens must be positive")
        if spec is not None and spec.block_tokens != block_tokens:
            raise ValueError("spec.block_tokens != pool block_tokens")
        if pooled and spec is None:
            raise ValueError("pooled layout needs a data-mode spec")
        self.block_tokens = block_tokens
        self.spec = spec
        self.pooled = pooled
        # cluster replicas pin payloads to their replica mesh instead
        # of the process-default device, so block arrays and the
        # replica's sharded params share one device set under jit
        self.sharding_fn = sharding_fn
        self._shardings: Dict[str, object] = {}
        self._zero = None          # shared zero block for the gather
        self.k_store = self.v_store = None
        if pooled:
            shape = (spec.n_units, spec.n_attn, num_blocks,
                     block_tokens, spec.n_kv, spec.head_dim)
            fast = self._sharding(FAST_KIND)
            self.k_store = jnp.zeros(shape, dtype=spec.dtype, device=fast)
            self.v_store = jnp.zeros(shape, dtype=spec.dtype, device=fast)
        self.slow_kind = slow_kind
        self.default_kind = default_kind or slow_kind
        self.blocks: List[KVBlock] = [
            KVBlock(bid=i, kind=self.default_kind)
            for i in range(num_blocks)]
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self.table: Dict[int, List[int]] = {}   # seq_id -> [bid]
        self.seq_len: Dict[int, int] = {}       # seq_id -> tokens written
        self.counters = PoolCounters()
        self.telemetry = None                   # AccessTrace/AccessSampler
        # residency accounting lives in the (possibly shared) ledger; a
        # private one is created for the single-tenant default
        from ..pool.ledger import ResidencyLedger
        self.ledger = ledger if ledger is not None else ResidencyLedger()
        self.tenant = tenant
        self.ledger.register_tenant(tenant)
        self.fast_block_budget = (num_blocks if fast_block_budget is None
                                  else fast_block_budget)

    # ------------------------------------------------------------------ #
    # telemetry                                                          #
    # ------------------------------------------------------------------ #
    def attach_telemetry(self, recorder) -> None:
        """Attach an access recorder (anything with ``observe(obj,
        read_bytes, write_bytes, random_fraction, phase)`` — an
        AccessTrace or an AccessSampler front-end)."""
        self.telemetry = recorder

    def _emit(self, seq_id: int, read_bytes: int = 0, write_bytes: int = 0,
              phase: str = "") -> None:
        if self.telemetry is not None and (read_bytes or write_bytes):
            self.telemetry.observe(f"seq{seq_id}", read_bytes, write_bytes,
                                   0.0, phase=phase)

    # ------------------------------------------------------------------ #
    # capacity accounting (occupancy reads/writes go through the ledger) #
    # ------------------------------------------------------------------ #
    def _obj(self, seq_id: int) -> str:
        return f"seq{seq_id}"

    @property
    def fast_block_budget(self) -> int:
        b = self.ledger.budget(self.tenant, FAST_KIND)
        return self.num_blocks if b is None else b // self.block_nbytes()

    @fast_block_budget.setter
    def fast_block_budget(self, n_blocks: int) -> None:
        self.ledger.set_budget(self.tenant, FAST_KIND,
                               int(n_blocks) * self.block_nbytes())

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    def free_block_count(self) -> int:
        return len(self._free)

    def used_block_count(self) -> int:
        return self.num_blocks - len(self._free)

    def blocks_on(self, kind: str) -> int:
        return self.ledger.bytes_on(kind, self.tenant) \
            // self.block_nbytes()

    def fast_used(self) -> int:
        return self.blocks_on(FAST_KIND)

    def occupancy(self) -> float:
        return self.used_block_count() / self.num_blocks

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.block_tokens))

    def block_nbytes(self) -> int:
        return self.spec.nbytes if self.spec is not None else 1

    # ------------------------------------------------------------------ #
    # alloc / free                                                       #
    # ------------------------------------------------------------------ #
    def can_alloc(self, n_blocks: int) -> bool:
        return len(self._free) >= n_blocks

    def alloc(self, seq_id: int, n_blocks: int = 1,
              kind=None) -> List[int]:
        """Append ``n_blocks`` fresh blocks to ``seq_id``'s table.

        ``kind`` may be a memory-kind string, ``None`` (pool default),
        or a zero-arg callable evaluated per block — how a static-split
        allocator interleaves kinds at block granularity.
        """
        if n_blocks > len(self._free):
            raise PoolExhausted(
                f"need {n_blocks} blocks, {len(self._free)} free")
        tbl = self.table.setdefault(seq_id, [])
        self.seq_len.setdefault(seq_id, 0)
        out = []
        bn = self.block_nbytes()
        for _ in range(n_blocks):
            k = kind() if callable(kind) else kind
            bid = self._free.pop()
            b = self.blocks[bid]
            b.seq_id = seq_id
            b.logical_idx = len(tbl)
            b.kind = k or self.default_kind
            b.touch_count = 0
            b.last_touch_step = -(10 ** 9)
            tbl.append(bid)
            out.append(bid)
            self.counters.allocs += 1
            self.ledger.record_alloc(self.tenant, self._obj(seq_id),
                                     b.kind, bn)
        return out

    def free_seq(self, seq_id: int) -> int:
        """Release every block of a sequence; returns #blocks freed."""
        tbl = self.table.pop(seq_id, [])
        self.seq_len.pop(seq_id, None)
        if self.telemetry is not None:
            forget = getattr(self.telemetry, "forget", None)
            if forget is not None:
                forget(f"seq{seq_id}")
        for bid in tbl:
            b = self.blocks[bid]
            b.seq_id = None
            b.logical_idx = -1
            b.kv = None
            self._free.append(bid)
            self.counters.frees += 1
        if tbl:
            self.ledger.retire(self.tenant, self._obj(seq_id))
        return len(tbl)

    def seq_blocks(self, seq_id: int) -> List[KVBlock]:
        return [self.blocks[bid] for bid in self.table.get(seq_id, [])]

    # ------------------------------------------------------------------ #
    # heat                                                               #
    # ------------------------------------------------------------------ #
    def touch_seq(self, seq_id: int, step: int) -> None:
        """Decode reads the whole block table of a sequence each step."""
        tbl = self.table.get(seq_id, [])
        for bid in tbl:
            b = self.blocks[bid]
            b.touch_count += 1
            b.last_touch_step = step
        self._emit(seq_id, read_bytes=len(tbl) * self.block_nbytes(),
                   phase="decode")

    # ------------------------------------------------------------------ #
    # payload I/O (data mode)                                            #
    # ------------------------------------------------------------------ #
    def _sharding(self, kind: str):
        sh = self._shardings.get(kind)
        if sh is None:
            if self.sharding_fn is not None:
                sh = self.sharding_fn(kind)
            else:
                from ..core.tiered_array import sharding_for_kind
                sh = sharding_for_kind(kind)
            self._shardings[kind] = sh
        return sh

    def _put(self, payloads: Sequence, srcs: Sequence[str],
             dsts: Sequence[str]) -> list:
        """Place each payload on its kind in ``dsts`` in one runtime
        call; count the blocks, and the call, that cross between a host
        kind and the device (``srcs``: where each payload is now)."""
        out = jax.device_put(list(payloads),
                             [self._sharding(d) for d in dsts])
        c, bn = self.counters, self.block_nbytes()
        h2d = sum(s != FAST_KIND and d == FAST_KIND
                  for s, d in zip(srcs, dsts))
        d2h = sum(s == FAST_KIND and d != FAST_KIND
                  for s, d in zip(srcs, dsts))
        c.h2d_bytes += h2d * bn
        c.h2d_puts += h2d
        c.h2d_calls += h2d > 0
        c.d2h_bytes += d2h * bn
        c.d2h_puts += d2h
        c.d2h_calls += d2h > 0
        return out

    def _zero_block(self):
        if self._zero is None:
            self._zero = jnp.zeros(self.spec.payload_shape,
                                   dtype=self.spec.dtype,
                                   device=self._sharding(FAST_KIND))
        return self._zero

    def write_prefill(self, seq_id: int, kv_k, kv_v, n_tokens: int,
                      kind: Optional[str] = None) -> None:
        """Split a contiguous prefill cache into this sequence's blocks.

        kv_k/kv_v: (U, n_attn, n_tokens, KV, hd) on the device — batch
        already squeezed.  Allocates exactly the blocks the tokens need,
        on ``kind``, and places their payloads there in one call.
        """
        bt = self.block_tokens
        n_blocks = self.blocks_for_tokens(n_tokens)
        with annotate("kv.write_prefill", blocks=n_blocks):
            bids = self.alloc(seq_id, n_blocks, kind=kind)
            if self.spec is not None:
                kv = _stack_padded(kv_k, kv_v, n_blocks * bt - n_tokens)
                self._write_blocks(bids, [kv[:, :, :, i * bt:(i + 1) * bt]
                                          for i in range(n_blocks)])
        self.seq_len[seq_id] = n_tokens
        self._emit(seq_id, write_bytes=n_blocks * self.block_nbytes(),
                   phase="prefill")

    def _write_blocks(self, bids: Sequence[int], payloads: Sequence
                      ) -> None:
        """Place payloads, which are on the device, on their blocks."""
        if self.pooled:
            # pooled layout: payloads live at the block's slot in the
            # persistent stores; residency is the ledger's (logical)
            for bid, kv in zip(bids, payloads):
                self.k_store = self.k_store.at[:, :, bid].set(
                    kv[0].astype(self.k_store.dtype))
                self.v_store = self.v_store.at[:, :, bid].set(
                    kv[1].astype(self.v_store.dtype))
            return
        blocks = [self.blocks[bid] for bid in bids]
        placed = self._put(payloads, [FAST_KIND] * len(blocks),
                           [b.kind for b in blocks])
        for b, kv in zip(blocks, placed):
            b.kv = kv

    def append_token(self, seq_id: int, k_tok, v_tok) -> None:
        """Write one new token's (k, v) at the tail of the sequence.

        k_tok/v_tok: (U, n_attn, KV, hd).  The caller must have allocated
        a tail block when ``seq_len % block_tokens == 0``.
        """
        with annotate("kv.append"):
            self._append_token(seq_id, k_tok, v_tok)

    def _append_token(self, seq_id: int, k_tok, v_tok) -> None:
        n = self.seq_len[seq_id]
        tbl = self.table[seq_id]
        blk_idx, off = divmod(n, self.block_tokens)
        if blk_idx >= len(tbl):
            raise PoolExhausted(
                f"seq {seq_id}: token {n} has no tail block")
        if self.pooled:
            bid = tbl[blk_idx]
            self.k_store = self.k_store.at[:, :, bid, off].set(
                k_tok.astype(self.k_store.dtype))
            self.v_store = self.v_store.at[:, :, bid, off].set(
                v_tok.astype(self.v_store.dtype))
        elif self.spec is not None:
            b = self.blocks[tbl[blk_idx]]
            # host memory kinds hold data, not compute: the update runs
            # on the device and the block goes back to its kind
            if b.kv is None:           # fresh tail block
                kv = jnp.zeros(self.spec.payload_shape,
                               dtype=self.spec.dtype,
                               device=self._sharding(FAST_KIND))
            elif b.kind == FAST_KIND:
                kv = b.kv
            else:
                (kv,) = self._put([b.kv], [b.kind], [FAST_KIND])
            kv = _write_token(kv, k_tok, v_tok, np.int32(off))
            if b.kind != FAST_KIND:
                (kv,) = self._put([kv], [FAST_KIND], [b.kind])
            b.kv = kv
        self.seq_len[seq_id] = n + 1
        self._emit(seq_id,
                   write_bytes=max(self.block_nbytes()
                                   // self.block_tokens, 1),
                   phase="decode")

    def gather_seq(self, seq_id: int, pad_blocks: int):
        """One contiguous payload on the fast kind, padded to
        ``pad_blocks``: shape (2, U, n_attn, pad_blocks*bt, KV, hd), K at
        index 0 and V at 1.  The sequence's host-resident blocks move in
        one call (``device_put`` of the list, which is async), so every
        block's transfer is issued before the concatenate waits on any.
        """
        assert self.spec is not None, "gather_seq needs a data-mode pool"
        tbl = self.table.get(seq_id, [])
        with annotate("kv.gather_seq", rid=seq_id, blocks=len(tbl)):
            return self._gather_seq(tbl, seq_id, pad_blocks)

    def _gather_seq(self, tbl: List[int], seq_id: int, pad_blocks: int):
        n_pad = pad_blocks - len(tbl)
        if n_pad < 0:
            raise ValueError(f"seq {seq_id} has {len(tbl)} blocks "
                             f"> pad_blocks={pad_blocks}")
        if self.pooled:
            # staging copy out of the pooled stores (the baseline the
            # fused path's gather_tables exists to avoid): take the
            # sequence's blocks, flatten to token order, zero-pad.
            # Positions past seq_len may hold a prior owner's stale
            # tokens — every consumer masks by kv_len.
            shape = list(self.spec.payload_shape)
            shape[3] = pad_blocks * self.block_tokens
            if not tbl:
                return jnp.zeros(tuple(shape), dtype=self.spec.dtype,
                                 device=self._sharding(FAST_KIND))
            idx = np.asarray(tbl, np.int32)

            def take(store):
                g = jnp.take(store, idx, axis=2)   # (U,n_attn,nb,bt,..)
                g = g.reshape(g.shape[0], g.shape[1], -1, *g.shape[4:])
                if n_pad:
                    pads = [(0, 0)] * g.ndim
                    pads[2] = (0, n_pad * self.block_tokens)
                    g = jnp.pad(g, pads)
                return g

            return jnp.stack([take(self.k_store), take(self.v_store)])
        blocks = [self.blocks[bid] for bid in tbl]
        host = [b for b in blocks
                if b.kv is not None and b.kind != FAST_KIND]
        moved = dict(zip([b.bid for b in host],
                         self._put([b.kv for b in host],
                                   [b.kind for b in host],
                                   [FAST_KIND] * len(host))))
        # a tail block allocated but not yet written reads as zeros;
        # the pad is the same shared zero block, so the concatenate
        # always takes pad_blocks operands and compiles once
        zero = self._zero_block()
        parts = [zero if b.kv is None else moved.get(b.bid, b.kv)
                 for b in blocks] + [zero] * n_pad
        return _concat_blocks(parts)

    def gather_tables(self, seq_ids: Sequence[int], pad_blocks: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Block-index tables for the fused tiered-gather kernel.

        Returns ``(tables, lens)``: ``tables`` is int32
        ``(len(seq_ids), pad_blocks)`` of physical block ids in logical
        order (pad slots hold block 0 — masked by ``lens``), ``lens``
        the per-sequence cached token counts.  This is the whole
        "gather": the kernel indexes ``k_store``/``v_store`` through it
        directly, no staging copy.
        """
        if not self.pooled:
            raise ValueError("gather_tables needs a pooled-layout pool")
        tables = np.zeros((len(seq_ids), pad_blocks), np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, sid in enumerate(seq_ids):
            tbl = self.table.get(sid, [])
            if len(tbl) > pad_blocks:
                raise ValueError(f"seq {sid} has {len(tbl)} blocks "
                                 f"> pad_blocks={pad_blocks}")
            tables[i, :len(tbl)] = tbl
            lens[i] = self.seq_len.get(sid, 0)
        return tables, lens

    # ------------------------------------------------------------------ #
    # migration                                                          #
    # ------------------------------------------------------------------ #
    def migrate(self, bid: int, kind: str) -> bool:
        """Move one block to ``kind``; returns False if it's a no-op.

        Promotions are gated by the ledger (``can_place``): the tenant's
        arbitrated fast-tier budget and any shared fast-tier capacity
        both bind, so pools sharing one ledger contend honestly.
        """
        b = self.blocks[bid]
        if b.free or b.kind == kind:
            return False
        bn = self.block_nbytes()
        was_fast = b.kind == FAST_KIND
        if kind == FAST_KIND and not was_fast:
            if not self.ledger.can_place(self.tenant, FAST_KIND, bn):
                return False
            self.counters.promoted += 1
        elif was_fast and kind != FAST_KIND:
            self.counters.demoted += 1
        self.ledger.record_move(self.tenant, self._obj(b.seq_id),
                                b.kind, kind, bn)
        src, b.kind = b.kind, kind
        self.counters.migrated_bytes += bn
        # pooled layout keeps payloads in place in device memory: its
        # residency is ledger bookkeeping only
        if self.spec is not None and not self.pooled and b.kv is not None:
            with annotate("kv.migrate"):
                (b.kv,) = self._put([b.kv], [src], [kind])
        return True

    # ------------------------------------------------------------------ #
    # defrag                                                             #
    # ------------------------------------------------------------------ #
    def defrag(self) -> int:
        """Compact live blocks to the lowest physical ids.

        After long run with churn, live blocks scatter across the id
        space; compaction keeps each sequence's physical blocks
        contiguous and in logical order (so a future DMA engine can use
        strided descriptors).  Payloads and residency move with the
        block.  Returns the number of blocks relocated.
        """
        live: List[KVBlock] = []
        for seq_id in sorted(self.table):
            live.extend(self.blocks[bid] for bid in self.table[seq_id])
        moved = 0
        new_blocks = [KVBlock(bid=i, kind=self.default_kind)
                      for i in range(self.num_blocks)]
        new_table: Dict[int, List[int]] = {s: [] for s in self.table}
        for i, old in enumerate(live):
            nb = new_blocks[i]
            if old.bid != i:
                moved += 1
            nb.kind = old.kind
            nb.seq_id = old.seq_id
            nb.logical_idx = old.logical_idx
            nb.kv = old.kv
            nb.touch_count = old.touch_count
            nb.last_touch_step = old.last_touch_step
            new_table[old.seq_id].append(i)
        if self.pooled and live:
            # permute the store rows with the block ids so slot i still
            # holds the payload of the block now labelled i
            perm = [old.bid for old in live]
            rest = [i for i in range(self.num_blocks)
                    if i not in set(perm)]
            idx = jnp.asarray(perm + rest, jnp.int32)
            self.k_store = jnp.take(self.k_store, idx, axis=2)
            self.v_store = jnp.take(self.v_store, idx, axis=2)
        self.blocks = new_blocks
        self.table = new_table
        self._free = list(range(self.num_blocks - 1, len(live) - 1, -1))
        self.counters.defrags += 1
        return moved


# ---------------------------------------------------------------------- #
# TieredKVCache: whole-cache tier residency for the one-shot engine.      #
# ---------------------------------------------------------------------- #
class TieredKVCache:
    """Static-split KV residency for FlexGenEngine (one-shot path).

    Owns the tier placement of a contiguous decode cache between steps:
    ``stash`` writes the cache back to its tier shares, ``restore``
    materializes it on device.  This is the degenerate single-request
    case of the paged pool (one 'block' per share span), kept so the
    one-shot engine and the paged engine share one KV-management home.
    """

    def __init__(self, shares: Sequence[Tuple[str, float]],
                 keys: Sequence[str] = ("kv_k", "kv_v"),
                 ledger=None, tenant: str = "oneshot_kv"):
        self.shares = list(shares)
        self.keys = list(keys)
        self._tiered: Dict[str, object] = {}
        from ..pool.ledger import ResidencyLedger
        self.ledger = ledger if ledger is not None else ResidencyLedger()
        self.tenant = tenant
        self.ledger.register_tenant(tenant)

    @property
    def offloaded(self) -> bool:
        return any(f > 0 for kind, f in self.shares if kind != FAST_KIND)

    def _sync_ledger(self, key: str) -> None:
        """Mirror one buffer's realized per-kind bytes into the ledger
        (the TieredArray's block rounding is the truth, not the asked
        shares)."""
        ta = self._tiered[key]
        placement = {k: ta.bytes_on(k) for k in set(ta.kinds)
                     if ta.bytes_on(k) > 0}
        if self.ledger.has(self.tenant, key):
            self.ledger.retire(self.tenant, key)
        self.ledger.register(self.tenant, key, placement)

    def stash(self, cache: Dict[str, object]) -> None:
        """Place the cache's KV buffers across the configured shares."""
        from ..core.tiered_array import TieredArray
        if not self.offloaded:
            return
        for key in self.keys:
            if key in cache:
                arr = cache[key]
                self._tiered[key] = TieredArray.place(
                    arr.reshape(arr.shape[0], -1), self.shares)
                self._sync_ledger(key)

    def restore(self, cache: Dict[str, object]) -> Dict[str, object]:
        """Materialize tier-resident KV back into the cache dict."""
        if not self.offloaded:
            return cache
        for key, ta in self._tiered.items():
            cache[key] = ta.gather().reshape(cache[key].shape)
        return cache

    def update(self, cache: Dict[str, object]) -> None:
        """Write a stepped cache back, preserving placement."""
        if not self.offloaded:
            return
        for key in self._tiered:
            self._tiered[key] = self._tiered[key].update(
                cache[key].reshape(cache[key].shape[0], -1))

    def bytes_on(self, kind: str) -> int:
        """Tier occupancy, read through the ledger (single source)."""
        return self.ledger.bytes_on(kind, self.tenant)


def spec_from_config(cfg, block_tokens: int) -> KVBlockSpec:
    """Derive the pool block spec from a ModelConfig (attn layers only)."""
    n_attn = len(cfg.unit_attn_layers)
    if n_attn == 0:
        raise ValueError(f"{cfg.name}: no attention layers to page")
    dtype = "int8" if cfg.kv_cache_dtype == "int8" else "bfloat16"
    return KVBlockSpec(n_units=cfg.n_units, n_attn=n_attn,
                       block_tokens=block_tokens, n_kv=cfg.n_kv,
                       head_dim=cfg.head_dim, dtype=dtype)
