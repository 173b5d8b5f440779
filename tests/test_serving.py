"""Serving subsystem: pool invariants, scheduler ordering, tiering,
and paged-decode consistency against the monolithic decode path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import (ContinuousBatchingScheduler, FAST_KIND,
                           KVBlockSpec, KVBlockTierer, PagedKVPool,
                           plan_admission, PoolExhausted, Request,
                           RequestState, SchedulerConfig, ServingConfig,
                           ServingEngine)


def _meta_pool(num_blocks=16, block_tokens=4, fast_budget=None, **kw):
    return PagedKVPool(num_blocks, block_tokens,
                       fast_block_budget=fast_budget, **kw)


def _req(rid, plen=6, new=4, arrival=0.0):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32),
                   max_new_tokens=new, arrival_s=arrival)


# ===================================================================== #
# Pool: alloc / free / defrag invariants                                #
# ===================================================================== #
def test_pool_alloc_free_roundtrip():
    pool = _meta_pool(8)
    a = pool.alloc(1, 3)
    b = pool.alloc(2, 2)
    assert len(set(a) | set(b)) == 5          # unique physical blocks
    assert pool.used_block_count() == 5
    assert pool.free_block_count() == 3
    assert [pool.blocks[x].logical_idx for x in a] == [0, 1, 2]
    assert pool.free_seq(1) == 3
    assert pool.used_block_count() == 2
    assert 1 not in pool.table
    # freed blocks are reusable
    c = pool.alloc(3, 5)
    assert len(c) == 5
    with pytest.raises(PoolExhausted):
        pool.alloc(4, 2)


def test_pool_blocks_for_tokens():
    pool = _meta_pool(8, block_tokens=4)
    assert pool.blocks_for_tokens(1) == 1
    assert pool.blocks_for_tokens(4) == 1
    assert pool.blocks_for_tokens(5) == 2


def test_pool_fast_budget_enforced():
    pool = _meta_pool(8, fast_budget=2)
    pool.alloc(1, 4)                          # default slow kind
    bids = pool.table[1]
    assert pool.migrate(bids[0], FAST_KIND)
    assert pool.migrate(bids[1], FAST_KIND)
    assert not pool.migrate(bids[2], FAST_KIND)   # budget full
    assert pool.fast_used() == 2
    assert pool.counters.promoted == 2
    assert pool.migrate(bids[0], "pinned_host")   # demote frees a slot
    assert pool.counters.demoted == 1
    assert pool.migrate(bids[2], FAST_KIND)


def test_pool_per_block_alloc_kind_callable():
    pool = _meta_pool(8, fast_budget=8)
    kinds = iter([FAST_KIND, "pinned_host", FAST_KIND, "pinned_host"])
    pool.alloc(1, 4, kind=lambda: next(kinds))
    got = [pool.blocks[b].kind for b in pool.table[1]]
    assert got == [FAST_KIND, "pinned_host", FAST_KIND, "pinned_host"]


def test_pool_defrag_compacts_and_preserves():
    pool = _meta_pool(12)
    pool.alloc(1, 3)
    pool.alloc(2, 4)
    pool.alloc(3, 2)
    pool.free_seq(2)                          # hole in the id space
    seq1, seq3 = list(pool.table[1]), list(pool.table[3])
    kinds1 = [pool.blocks[b].kind for b in seq1]
    pool.blocks[seq3[0]].touch_count = 7      # payload metadata survives
    moved = pool.defrag()
    assert moved >= 0
    # live blocks occupy the lowest ids, free list is the suffix
    live = sorted(bid for tbl in pool.table.values() for bid in tbl)
    assert live == list(range(5))
    assert sorted(pool._free) == list(range(5, 12))
    # logical order and metadata preserved
    assert [pool.blocks[b].logical_idx for b in pool.table[1]] == [0, 1, 2]
    assert [pool.blocks[b].kind for b in pool.table[1]] == kinds1
    assert pool.blocks[pool.table[3][0]].touch_count == 7
    # allocation still works after compaction
    pool.alloc(4, 7)
    with pytest.raises(PoolExhausted):
        pool.alloc(5, 1)


# ===================================================================== #
# gather_seq / gather_tables edge cases (data mode, both layouts)       #
# ===================================================================== #
def _data_pool(pooled=False, num_blocks=6, bt=4):
    spec = KVBlockSpec(n_units=1, n_attn=2, block_tokens=bt, n_kv=2,
                       head_dim=8, dtype="float32")
    return PagedKVPool(num_blocks, bt, spec=spec, pooled=pooled), spec


def test_gather_seq_requires_data_mode():
    pool = _meta_pool(8)                      # metadata-only: no spec
    pool.alloc(1, 2)
    with pytest.raises(AssertionError, match="data-mode"):
        pool.gather_seq(1, 4)


@pytest.mark.parametrize("pooled", [False, True])
def test_gather_seq_empty_sequence_is_zero_padded(pooled):
    pool, spec = _data_pool(pooled)
    kv = pool.gather_seq(99, 3)               # unknown seq: no blocks
    assert kv.shape == (2, 1, 2, 3 * 4, 2, 8)  # K at 0, V at 1
    assert float(jnp.abs(kv).sum()) == 0.0


@pytest.mark.parametrize("pooled", [False, True])
def test_gather_seq_rejects_pad_shorter_than_live_blocks(pooled):
    pool, spec = _data_pool(pooled)
    pool.alloc(1, 3)
    with pytest.raises(ValueError, match="pad_blocks"):
        pool.gather_seq(1, 2)


@pytest.mark.parametrize("pooled", [False, True])
def test_gather_seq_roundtrips_written_payload(pooled):
    pool, spec = _data_pool(pooled)
    rs = np.random.RandomState(0)
    kv_k = jnp.asarray(rs.randn(1, 2, 6, 2, 8), jnp.float32)
    kv_v = jnp.asarray(rs.randn(1, 2, 6, 2, 8), jnp.float32)
    pool.write_prefill(7, kv_k, kv_v, n_tokens=6)
    k, v = pool.gather_seq(7, 3)              # 2 live blocks + 1 pad
    np.testing.assert_allclose(np.asarray(k[:, :, :6]),
                               np.asarray(kv_k), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v[:, :, :6]),
                               np.asarray(kv_v), rtol=1e-6)
    assert float(jnp.abs(k[:, :, 8:]).sum()) == 0.0   # pad block zero


def test_append_token_keeps_block_in_its_memory_kind():
    """The tail-block update runs on the device, and the block goes
    back to its own kind; the gathered copy lands on the device."""
    pool, spec = _data_pool(pooled=False)
    rs = np.random.RandomState(1)
    kv_k = jnp.asarray(rs.randn(1, 2, 3, 2, 8), jnp.float32)
    pool.write_prefill(5, kv_k, kv_k, n_tokens=3, kind="pinned_host")
    tok = jnp.asarray(rs.randn(1, 2, 2, 8), jnp.float32)
    pool.append_token(5, tok, -tok)             # fills the block's tail
    pool.alloc(5, 1, kind="pinned_host")
    pool.append_token(5, tok, tok)              # a fresh tail block
    for b in pool.seq_blocks(5):
        assert b.kv.sharding.memory_kind == "pinned_host"
        assert b.kv.shape == spec.payload_shape and b.k is b.kv
    kv = pool.gather_seq(5, 3)
    assert kv.sharding.memory_kind == "device"
    k, v = kv
    np.testing.assert_array_equal(np.asarray(k[:, :, :3]),
                                  np.asarray(kv_k))
    np.testing.assert_array_equal(np.asarray(k[:, :, 3]), np.asarray(tok))
    np.testing.assert_array_equal(np.asarray(v[:, :, 3]),
                                  np.asarray(-tok))
    np.testing.assert_array_equal(np.asarray(k[:, :, 4]), np.asarray(tok))
    assert float(jnp.abs(k[:, :, 5:]).sum()) == 0.0


# one buffer a block, one call a sequence: the staged gather against a
# per-block reference, and each path that moves a payload round-trips it
MIXED = ["pinned_host", FAST_KIND, "unpinned_host", FAST_KIND]


def _written_seq(pool, kinds, seq_id=3, seed=2):
    """Prefill ``len(kinds)`` blocks of ``seq_id``, block i on kinds[i],
    then allocate one tail block (on kinds[0]) that is not written.
    Returns the prefill's K and V, (1, 2, n_tokens, 2, 8) each."""
    rs = np.random.RandomState(seed)
    n = len(kinds) * pool.block_tokens
    kv_k = jnp.asarray(rs.randn(1, 2, n, 2, 8), jnp.float32)
    kv_v = jnp.asarray(rs.randn(1, 2, n, 2, 8), jnp.float32)
    it = iter(kinds)
    pool.write_prefill(seq_id, kv_k, kv_v, n_tokens=n,
                       kind=lambda: next(it))
    pool.alloc(seq_id, 1, kind=kinds[0])
    return kv_k, kv_v


def _puts(pool):
    c = pool.counters
    return c.h2d_puts, c.h2d_calls, c.d2h_puts, c.d2h_calls


@pytest.mark.parametrize("kinds", [MIXED, [FAST_KIND] * 4,
                                   ["pinned_host"] * 4])
def test_gather_seq_matches_a_per_block_reference(kinds):
    pool, spec = _data_pool(num_blocks=8)
    _written_seq(pool, kinds)
    blocks = pool.seq_blocks(3)
    assert [b.kind for b in blocks[:-1]] == kinds
    assert blocks[-1].kv is None              # the unwritten tail
    before = _puts(pool)
    kv = pool.gather_seq(3, 7)                # 5 blocks + 2 pad
    n_host = sum(k != FAST_KIND for k in kinds)
    h2d, calls, d2h, d2h_calls = np.subtract(_puts(pool), before)
    assert (h2d, calls) == (n_host, 1 if n_host else 0)
    assert (d2h, d2h_calls) == (0, 0)
    assert kv.sharding.memory_kind == FAST_KIND
    zero = np.zeros(spec.payload_shape, np.float32)
    ref = np.concatenate([np.asarray(b.kv) if b.kv is not None else zero
                          for b in blocks] + [zero, zero], axis=3)
    np.testing.assert_array_equal(np.asarray(kv), ref)
    # the blocks stay where they were
    assert [b.kv.sharding.memory_kind for b in blocks[:-1]] == kinds


def test_gather_seq_moves_each_sequence_in_one_call():
    pool, _ = _data_pool(num_blocks=16)
    _written_seq(pool, MIXED, seq_id=1)
    _written_seq(pool, [FAST_KIND] * 2, seq_id=2)
    _written_seq(pool, ["pinned_host"] * 3, seq_id=4)
    before = _puts(pool)
    for sid in (1, 2, 4):
        pool.gather_seq(sid, 6)
    assert tuple(np.subtract(_puts(pool), before)) == (2 + 0 + 3, 2, 0, 0)


@pytest.mark.parametrize("kinds", [MIXED, ["pinned_host"] * 4])
def test_write_prefill_places_each_block_bitwise(kinds):
    pool, spec = _data_pool(num_blocks=8)
    kv_k, kv_v = _written_seq(pool, kinds)
    n_host = sum(k != FAST_KIND for k in kinds)
    assert _puts(pool) == (0, 0, n_host, 1 if n_host else 0)
    want = np.stack([np.asarray(kv_k), np.asarray(kv_v)])
    for i, b in enumerate(pool.seq_blocks(3)[:-1]):
        assert b.kv.sharding.memory_kind == kinds[i]
        np.testing.assert_array_equal(np.asarray(b.kv),
                                      want[:, :, :, 4 * i:4 * (i + 1)])


@pytest.mark.parametrize("kind", ["pinned_host", "unpinned_host",
                                  FAST_KIND])
def test_append_token_round_trips_the_payload(kind):
    pool, spec = _data_pool(num_blocks=8)
    rs = np.random.RandomState(3)
    kv_k = jnp.asarray(rs.randn(1, 2, 6, 2, 8), jnp.float32)
    kv_v = jnp.asarray(rs.randn(1, 2, 6, 2, 8), jnp.float32)
    pool.write_prefill(1, kv_k, kv_v, n_tokens=6, kind=kind)
    tail = pool.seq_blocks(1)[1]
    want = np.array(tail.kv)
    toks = [jnp.asarray(rs.randn(2, 1, 2, 2, 8), jnp.float32)
            for _ in range(3)]
    before = _puts(pool)
    for t in toks[:2]:                          # into the written tail
        pool.append_token(1, t[0], t[1])
    want[:, :, :, 2:4] = np.stack([np.asarray(t) for t in toks[:2]],
                                  axis=3)
    pool.alloc(1, 1, kind=kind)
    pool.append_token(1, toks[2][0], toks[2][1])  # a fresh tail block
    host = kind != FAST_KIND
    # in and out once a written block, out once the fresh one
    assert tuple(np.subtract(_puts(pool), before)) == \
        ((2, 2, 3, 3) if host else (0, 0, 0, 0))
    np.testing.assert_array_equal(np.asarray(tail.kv), want)
    fresh = pool.seq_blocks(1)[2]
    assert fresh.kv.sharding.memory_kind == kind
    assert tail.kv.sharding.memory_kind == kind
    got = np.asarray(fresh.kv)
    np.testing.assert_array_equal(got[:, :, :, 0], np.asarray(toks[2]))
    assert not got[:, :, :, 1:].any()
    assert pool.seq_len[1] == 9


def test_migrate_round_trips_the_payload():
    pool, _ = _data_pool(num_blocks=8)
    _written_seq(pool, ["pinned_host"])
    b = pool.seq_blocks(3)[0]
    want = np.asarray(b.kv)
    before = _puts(pool)
    for kind in (FAST_KIND, "unpinned_host", FAST_KIND, "pinned_host"):
        assert pool.migrate(b.bid, kind)
        assert b.kv.sharding.memory_kind == kind
        np.testing.assert_array_equal(np.asarray(b.kv), want)
    assert tuple(np.subtract(_puts(pool), before)) == (2, 2, 2, 2)


def test_defrag_keeps_each_payload_with_its_block():
    pool, _ = _data_pool(num_blocks=12)
    _written_seq(pool, ["pinned_host"] * 2, seq_id=1)
    _written_seq(pool, MIXED, seq_id=2, seed=5)
    pool.free_seq(1)
    before = {(b.seq_id, b.logical_idx): (b.kind, np.asarray(b.kv))
              for b in pool.blocks if b.kv is not None}
    ref = np.asarray(pool.gather_seq(2, 6))
    assert pool.defrag() > 0
    assert pool.table[2] == list(range(5))
    after = {(b.seq_id, b.logical_idx): (b.kind, np.asarray(b.kv))
             for b in pool.blocks if b.kv is not None}
    assert before.keys() == after.keys()
    for key, (kind, kv) in before.items():
        assert after[key][0] == kind
        assert pool.blocks[pool.table[2][key[1]]].kv.sharding \
            .memory_kind == kind
        np.testing.assert_array_equal(after[key][1], kv)
    np.testing.assert_array_equal(np.asarray(pool.gather_seq(2, 6)), ref)


def test_gather_tables_requires_pooled_layout():
    pool, _ = _data_pool(pooled=False)
    pool.alloc(1, 2)
    with pytest.raises(ValueError, match="pooled"):
        pool.gather_tables([1], 4)


def test_gather_tables_block_ids_and_lens():
    pool, _ = _data_pool(pooled=True)
    pool.alloc(1, 2)
    pool.seq_len[1] = 7
    pool.alloc(2, 1)
    pool.seq_len[2] = 3
    tbl, lens = pool.gather_tables([1, 2, 99], 3)
    assert tbl.shape == (3, 3) and tbl.dtype == np.int32
    assert list(tbl[0, :2]) == list(pool.table[1])
    assert tbl[0, 2] == 0                     # pad slot masked by lens
    assert list(lens) == [7, 3, 0]
    with pytest.raises(ValueError, match="pad_blocks"):
        pool.gather_tables([1], 1)


# ===================================================================== #
# Scheduler: admission + preemption ordering                            #
# ===================================================================== #
def test_scheduler_fifo_admission_capped_by_batch():
    pool = _meta_pool(32)
    sched = ContinuousBatchingScheduler(pool, SchedulerConfig(
        max_batch=2, max_prefill_per_iter=4))
    for i in range(4):
        sched.submit(_req(i))
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0, 1]     # FIFO, batch-capped
    assert [r.rid for r in sched.waiting] == [2, 3]


def test_scheduler_admission_respects_blocks_and_arrival():
    pool = _meta_pool(4, block_tokens=4)
    sched = ContinuousBatchingScheduler(pool, SchedulerConfig(
        max_batch=4, max_prefill_per_iter=4, admission_margin_blocks=1))
    sched.submit(_req(0, plen=7))      # needs 2 blocks (+1 margin)
    sched.submit(_req(1, plen=7, arrival=5.0))
    admitted = sched.admit(now_s=0.0)
    assert [r.rid for r in admitted] == [0]        # rid1 hasn't arrived
    pool.alloc(0, 2)
    admitted = sched.admit(now_s=10.0)
    assert admitted == []                          # 2 free < need 2+1
    pool.free_seq(0)
    assert [r.rid for r in sched.admit(now_s=10.0)] == [1]


def test_scheduler_preemption_lifo_and_readmission_order():
    pool = _meta_pool(8, block_tokens=4)
    sched = ContinuousBatchingScheduler(pool, SchedulerConfig(
        max_batch=3, max_prefill_per_iter=3))
    for i in range(3):
        sched.submit(_req(i, plen=6))
    admitted = sched.admit()
    assert len(admitted) == 3
    for r in admitted:
        pool.alloc(r.rid, 2)
    sched.submit(_req(3))
    # demand blocks: latest-admitted (rid2) must be evicted first
    victims = sched.preempt_for_blocks(5)
    assert [v.rid for v in victims] == [2, 1]
    assert all(v.state is RequestState.PREEMPTED for v in victims)
    assert pool.free_block_count() >= 5
    # preempted requests sit at the queue FRONT, most recent first,
    # ahead of the never-admitted rid3
    assert [r.rid for r in sched.waiting] == [1, 2, 3]
    assert victims[0].preemptions == 1


def test_scheduler_protected_request_evicted_last():
    pool = _meta_pool(8, block_tokens=4)
    sched = ContinuousBatchingScheduler(pool, SchedulerConfig(
        max_batch=2, max_prefill_per_iter=2))
    for i in range(2):
        sched.submit(_req(i, plen=6))
    admitted = sched.admit()
    for r in admitted:
        pool.alloc(r.rid, 4)
    protect = admitted[1]                  # newest would normally go first
    victims = sched.preempt_for_blocks(4, protect=protect)
    assert [v.rid for v in victims] == [0]
    assert protect.state is RequestState.RUNNING


# ===================================================================== #
# Tiering                                                               #
# ===================================================================== #
def test_tiering_static_never_migrates():
    pool = _meta_pool(8, fast_budget=4)
    pool.alloc(1, 4)
    tierer = KVBlockTierer(pool, "static")
    pool.touch_seq(1, 0)
    assert tierer.step([1], 0) == 0
    assert pool.fast_used() == 0


@pytest.mark.parametrize("policy", ["autonuma", "tiering08", "tpp"])
def test_tiering_promotes_hot_within_budget(policy):
    pool = _meta_pool(12, fast_budget=4)
    pool.alloc(1, 4)
    pool.alloc(2, 4)
    tierer = KVBlockTierer(pool, policy)
    for step in range(6):                   # seq1 hot, seq2 cold
        pool.touch_seq(1, step)
        tierer.step([1], step)
    assert pool.fast_used() <= 4
    assert sum(1 for b in pool.seq_blocks(1) if b.kind == FAST_KIND) > 0
    assert all(b.kind != FAST_KIND for b in pool.seq_blocks(2))
    assert tierer.stats.promoted > 0
    assert tierer.stats.hint_faults > 0


def test_tiering_demotes_cold_on_pressure():
    pool = _meta_pool(12, fast_budget=2)
    pool.alloc(1, 2)
    pool.alloc(2, 2)
    tierer = KVBlockTierer(pool, "autonuma")
    # seq1 becomes hot and takes the whole fast budget
    for step in range(3):
        pool.touch_seq(1, step)
        tierer.step([1], step)
    assert all(b.kind == FAST_KIND for b in pool.seq_blocks(1))
    # now only seq2 is hot: seq1's cold blocks must be demoted
    for step in range(3, 7):
        pool.touch_seq(2, step)
        tierer.step([2], step)
    assert pool.fast_used() <= 2
    assert sum(1 for b in pool.seq_blocks(2) if b.kind == FAST_KIND) > 0
    assert tierer.stats.demoted > 0


# ===================================================================== #
# Admission plan (cost-model sizing)                                    #
# ===================================================================== #
def test_plan_admission_scales_with_capacity():
    cfg = get_smoke_config("llama3-8b")
    small = plan_admission(cfg, 16, 128, device_budget_bytes=2 * 2**20,
                           host_budget_bytes=2 * 2**20)
    big = plan_admission(cfg, 16, 128, device_budget_bytes=2 * 2**20,
                         host_budget_bytes=32 * 2**20)
    assert big.total_blocks > small.total_blocks
    assert big.max_batch >= small.max_batch    # LIO 3
    assert small.fast_blocks <= small.total_blocks


# ===================================================================== #
# Paged decode consistency + end-to-end engine                          #
# ===================================================================== #
@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("llama3-8b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_paged_decode_matches_monolithic(tiny):
    """Greedy tokens from the paged engine == lm.decode_step chain."""
    cfg, params = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0,
                              cfg.vocab)
    logits_p, cache = lm.prefill(params, cfg, toks)
    pads = [(0, 0)] * cache["kv_k"].ndim
    pads[3] = (0, 8)
    for k in ("kv_k", "kv_v"):
        cache[k] = jnp.pad(cache[k], pads)
    ref = [int(jnp.argmax(logits_p))]
    tok = jnp.argmax(logits_p, -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        lg, cache = lm.decode_step(params, cfg, cache, tok)
        ref.append(int(jnp.argmax(lg)))
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)

    eng = ServingEngine(cfg, params, ServingConfig(
        block_tokens=8, max_batch=2, max_context=32, policy="tiering08"))
    eng.submit(np.asarray(toks[0]), max_new_tokens=5)
    eng.run()
    assert eng.sched.finished[0].out_tokens == ref


def test_engine_multi_request_trace(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, ServingConfig(
        block_tokens=8, max_batch=2, max_context=32, policy="tiering08"))
    rs = np.random.RandomState(0)
    for i in range(3):
        eng.submit(rs.randint(0, cfg.vocab, (8,)).astype(np.int32),
                   max_new_tokens=4, arrival_s=0.005 * i)
    rep = eng.run()
    s = rep.summary
    assert s["finished"] == 3.0
    assert s["decode_tokens"] == 12.0
    assert s["throughput_tok_s"] > 0
    assert all(row["new_tokens"] == 4.0 for _, row in rep.per_request)
    assert all(row["decode_tok_s"] > 0 for _, row in rep.per_request)
    # every block returned to the pool
    assert eng.pool.used_block_count() == 0
    assert rep.tiering["promoted"] >= 0


def test_engine_preemption_under_tight_pool(tiny):
    """Pool smaller than the trace working set forces preemption, and
    every request still finishes with the full token count."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, ServingConfig(
        block_tokens=8, max_batch=3, max_context=24, policy="static",
        num_blocks=5, fast_block_budget=2))
    rs = np.random.RandomState(1)
    for i in range(3):
        eng.submit(rs.randint(0, cfg.vocab, (8,)).astype(np.int32),
                   max_new_tokens=10)
    rep = eng.run()
    assert rep.summary["finished"] == 3.0
    assert all(row["new_tokens"] == 10.0 for _, row in rep.per_request)
    assert rep.summary["preemptions"] > 0
    assert eng.pool.used_block_count() == 0


def test_engine_rejects_hybrid_arch():
    cfg = get_smoke_config("jamba-1.5-large-398b")
    with pytest.raises(ValueError, match="attention-only"):
        ServingEngine(cfg, params=None)


# ===================================================================== #
# Fused tiered-gather decode path                                       #
# ===================================================================== #
def _run_engine(cfg, params, prompts, new_tokens=4, **sv_kw):
    eng = ServingEngine(cfg, params, ServingConfig(
        block_tokens=8, max_batch=3, max_context=32, policy="tiering08",
        **sv_kw))
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    eng.run()
    return eng


def test_fused_gather_matches_staged_decode(tiny):
    """fused_gather=True must emit the same greedy tokens as the
    staged gather_seq path — the layouts differ, the math must not."""
    cfg, params = tiny
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (12, 7, 9)]
    staged = _run_engine(cfg, params, prompts)
    fused = _run_engine(cfg, params, prompts, fused_gather=True)
    assert fused.pool.pooled and not staged.pool.pooled
    for rid in range(3):
        assert (fused.sched.finished[rid].out_tokens
                == staged.sched.finished[rid].out_tokens)


@pytest.fixture(scope="module")
def tiny_moe():
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_fused_gather_moe_matches_staged(tiny_moe):
    cfg, params = tiny_moe
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (10, 6)]
    staged = _run_engine(cfg, params, prompts)
    fused = _run_engine(cfg, params, prompts, fused_gather=True)
    for rid in range(2):
        assert (fused.sched.finished[rid].out_tokens
                == staged.sched.finished[rid].out_tokens)


def test_fused_gather_moe_expert_telemetry(tiny_moe):
    """The fused path feeds routed expert ids into the ExpertPool:
    heat accumulates, residency stays within budget, and the summary
    surfaces the expert.* counters."""
    cfg, params = tiny_moe
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, cfg.vocab, (8,)).astype(np.int32)
               for _ in range(2)]
    eng = _run_engine(cfg, params, prompts, new_tokens=6,
                      fused_gather=True, expert_policy="lru",
                      expert_fast_fraction=0.25)
    ep = eng.expert_pool
    assert ep is not None
    # 2 requests x 5 decode iterations (the first output token comes
    # from prefill) x top_k activations x n_moe layers
    n_moe = ep.n_layers
    assert ep.counters.accesses == 2 * 5 * cfg.top_k * n_moe
    assert ep.fast_residents() <= ep.fast_expert_budget
    assert ep.counters.promoted > 0
    s = eng.telemetry_summary()
    assert s["expert.accesses"] == float(ep.counters.accesses)
    assert "expert.fast_hit_ratio" in s


def test_expert_policy_requires_moe_model(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="no MoE"):
        ServingEngine(cfg, params, ServingConfig(
            block_tokens=8, max_batch=2, max_context=32,
            expert_policy="lru"))


# ===================================================================== #
# Contention-aware admission (repro.topology)                           #
# ===================================================================== #
def _narrow_link_topology(bw_GBps=5.0):
    from repro.topology import TopologyGraph
    g = TopologyGraph("pcie", origin="hbm")
    g.add_node("hbm", "chip", tier=FAST_KIND)
    g.add_node("host", "host", tier="pinned_host")
    g.add_link("hbm", "host", 600.0, bw_GBps, "pcie")
    return g


def test_admission_budgets_shared_link():
    """Block capacity alone would admit everything; the KV gathers'
    shared PCIe link must cap the batch instead."""
    from repro.serving.kv_pool import KVBlockSpec
    spec = KVBlockSpec(n_units=2, n_attn=2, block_tokens=4, n_kv=2,
                       head_dim=8)                 # 1 KiB per block
    pool = PagedKVPool(64, 4, spec=spec)
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_batch=8, max_prefill_per_iter=8,
                              link_efficiency_floor=0.9,
                              gather_period_s=1e-6),
        topology=_narrow_link_topology(5.0))
    for i in range(6):
        sched.submit(_req(i, plen=6))
    admitted = sched.admit()
    # each request offers ~2 GB/s of gather over a 5 GB/s link: the
    # third would drag everyone under the 90% floor
    assert len(admitted) == 2
    assert sched.link_deferrals == 1
    assert len(sched.waiting) == 4
    # pool capacity was NOT the limit
    assert pool.can_alloc(sched.blocks_needed(sched.waiting[0]) + 1)


def test_admission_link_budget_counts_running_residency():
    """Running requests' slow-resident blocks load the link; requests
    whose blocks were promoted to the fast kind stop loading it."""
    from repro.serving.kv_pool import KVBlockSpec
    spec = KVBlockSpec(n_units=2, n_attn=2, block_tokens=4, n_kv=2,
                       head_dim=8)
    pool = PagedKVPool(64, 4, spec=spec, fast_block_budget=64,
                       default_kind="pinned_host")
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_batch=8, max_prefill_per_iter=1,
                              link_efficiency_floor=0.9,
                              gather_period_s=1e-6),
        topology=_narrow_link_topology(5.0))
    for i in range(3):
        sched.submit(_req(i, plen=6))
    first = sched.admit()
    assert len(first) == 1
    pool.alloc(first[0].rid, 2)                  # its KV lands slow
    second = sched.admit()
    assert len(second) == 1
    pool.alloc(second[0].rid, 2)
    assert sched.admit() == []                   # link saturated
    # promote one running request's blocks to the fast kind: its
    # gather leaves the PCIe link, freeing budget for the third
    for bid in pool.table[first[0].rid]:
        assert pool.migrate(bid, FAST_KIND)
    assert len(sched.admit()) == 1


def test_admission_without_topology_unchanged():
    pool = _meta_pool(32)
    sched = ContinuousBatchingScheduler(pool, SchedulerConfig(
        max_batch=8, max_prefill_per_iter=8))
    for i in range(4):
        sched.submit(_req(i))
    assert len(sched.admit()) == 4
    assert sched.link_deferrals == 0


def test_admission_ignores_preexisting_violations_on_disjoint_links():
    """A flow already under the floor (heavy residency on one link)
    must not head-of-line-block a candidate whose gather rides a
    different, healthy link — only the marginal effect counts."""
    from repro.serving.kv_pool import KVBlockSpec
    from repro.topology import TopologyGraph
    g = TopologyGraph("two-links", origin="hbm")
    g.add_node("hbm", "chip", tier=FAST_KIND)
    g.add_node("host1", "host", tier="pinned_host")
    g.add_node("host2", "host", tier="unpinned_host")
    g.add_link("hbm", "host1", 600.0, 5.0, "pcie")    # saturated below
    g.add_link("hbm", "host2", 900.0, 100.0, "pcie")  # plenty free
    spec = KVBlockSpec(n_units=2, n_attn=2, block_tokens=4, n_kv=2,
                       head_dim=8)                    # 1 KiB per block
    pool = PagedKVPool(64, 4, spec=spec, default_kind="unpinned_host")
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_batch=8, max_prefill_per_iter=2,
                              link_efficiency_floor=0.9,
                              gather_period_s=1e-6),
        topology=g)
    # two running requests whose 3 blocks each gather over the narrow
    # link: 2 x ~3 GB/s offered over 5 GB/s -> both already < 90%
    for rid in (10, 11):
        r = _req(rid, plen=10)
        r.state = RequestState.RUNNING
        sched.running.append(r)
        pool.alloc(rid, 3, kind="pinned_host")
    sched.submit(_req(0, plen=6))      # gathers over the wide link
    assert [r.rid for r in sched.admit()] == [0]
    assert sched.link_deferrals == 0


def test_admission_candidate_exactly_at_floor_is_admitted():
    """The floor is inclusive: a candidate whose fair share lands
    exactly on ``floor * offered`` is admitted, not deferred."""
    from repro.serving.kv_pool import KVBlockSpec
    spec = KVBlockSpec(n_units=2, n_attn=2, block_tokens=4, n_kv=2,
                       head_dim=8)                 # 1 KiB per block
    pool = PagedKVPool(64, 4, spec=spec)
    # one request = 2 blocks = 2.048 GB/s of gather; the link carries
    # exactly one request, so two equal flows each achieve *exactly*
    # half their offered rate (floats halve exactly) — the boundary
    bw = 2 * spec.nbytes / 1e-6 / 1e9
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_batch=8, max_prefill_per_iter=8,
                              link_efficiency_floor=0.5,
                              gather_period_s=1e-6),
        topology=_narrow_link_topology(bw))
    for i in range(3):
        sched.submit(_req(i, plen=6))
    admitted = sched.admit()
    # 1st flows free; 2nd lands exactly at the 50% floor (admitted);
    # 3rd would drop everyone to 1/3 < floor (deferred)
    assert [r.rid for r in admitted] == [0, 1]
    assert sched.link_deferrals == 1


def test_admission_skips_link_budget_for_fast_resident_default():
    """A pool whose default kind IS the fast kind gathers nothing over
    the topology: admission must not synthesize a zero flow."""
    pool = _meta_pool(32, fast_budget=32, default_kind=FAST_KIND)
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_batch=8, max_prefill_per_iter=8,
                              link_efficiency_floor=0.9,
                              gather_period_s=1e-6),
        topology=_narrow_link_topology(0.001))     # starved link
    for i in range(4):
        sched.submit(_req(i))
    assert len(sched.admit()) == 4
    assert sched.link_deferrals == 0


# ===================================================================== #
# Violation-predictive admission + preemption (repro.obs.qos)           #
# ===================================================================== #
class _StubPredictor:
    """Predictor double: violation iff total offered exceeds a limit."""

    def __init__(self, limit_GBps):
        self.limit = limit_GBps
        self.excludes = []

    def violations(self, flows, exclude=None):
        self.excludes.append(exclude)
        total = sum(f.offered_GBps for f in flows)
        return {"victim": (total, self.limit)} if total > self.limit \
            else {}

    def admission_ok(self, flows, exclude=None):
        return not self.violations(flows, exclude)


def _qos_sched(limit_GBps, **cfg_kw):
    from repro.serving.kv_pool import KVBlockSpec
    spec = KVBlockSpec(n_units=2, n_attn=2, block_tokens=4, n_kv=2,
                       head_dim=8)                 # 1 KiB per block
    pool = PagedKVPool(64, 4, spec=spec, default_kind="pinned_host",
                       tenant="antagonist")
    pred = _StubPredictor(limit_GBps)
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_batch=8, max_prefill_per_iter=8,
                              gather_period_s=1e-6, **cfg_kw),
        topology=_narrow_link_topology(100.0), predictor=pred)
    return sched, pool, pred


def test_qos_admission_defers_on_predicted_violation():
    # each request gathers ~2 GB/s; the stub allows 4.5 GB/s total
    sched, pool, pred = _qos_sched(4.5)
    for i in range(4):
        sched.submit(_req(i, plen=6))
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0, 1]
    assert sched.qos_deferrals == 1
    # the predictor replaces the floor entirely
    assert sched.link_deferrals == 0
    # own stale blame-book snapshot is excluded (live flows passed in)
    assert set(pred.excludes) == {"antagonist"}


def test_qos_preemption_sheds_slow_holders_until_forecast_clears():
    sched, pool, pred = _qos_sched(10.0)
    for prio, rid in ((1.0, 0), (0.0, 1), (2.0, 2)):
        r = _req(rid, plen=6)
        r.priority = prio
        sched.submit(r)
    admitted = sched.admit()
    assert len(admitted) == 3
    for r in admitted:
        pool.alloc(r.rid, 2)         # slow-resident: 3 x ~2 GB/s live
    # the SLO forecast tightens: only ~2 GB/s of gather is tolerable
    pred.limit = 2.5
    victims = sched.preempt_predicted_violation()
    # lowest priority evicted first, then the next, until it clears
    assert [v.rid for v in victims] == [1, 0]
    assert sched.slo_preemptions == 2
    assert [r.rid for r in sched.running] == [2]
    # evicted requests lose their blocks and rejoin the queue front
    assert pool.used_block_count() == 2
    assert [r.rid for r in sched.waiting] == [0, 1]
    # a second call is a no-op (forecast already clear)
    assert sched.preempt_predicted_violation() == []


def test_qos_preemption_noop_without_slow_holders():
    sched, pool, pred = _qos_sched(10.0)
    sched.submit(_req(0, plen=6))
    (r,) = sched.admit()
    pool.alloc(r.rid, 2, kind=FAST_KIND)   # all fast: no link traffic
    pred.limit = 0.0
    # running flows are empty (nothing slow-resident) -> nothing to shed
    assert sched.preempt_predicted_violation() == []
    assert sched.slo_preemptions == 0
