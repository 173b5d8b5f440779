"""Spans and counters of the serving loop on the profiler's clock:
``obs.trace.annotate`` and the recorder's spans, the pool's transfer
counters, the spans a profiled engine run leaves in the trace, and the
names and scopes of the jitted programs."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.obs import annotate, TraceRecorder
from repro.serving import (KVBlockSpec, PagedKVPool, ServingConfig,
                           ServingEngine)
from repro.serving.metrics import ServingMetrics

COUNTERS = ("tokens_out", "prefill_tokens", "kv_h2d_bytes",
            "kv_d2h_bytes", "kv_h2d_puts", "kv_d2h_puts", "kv_h2d_calls",
            "kv_d2h_calls")
PROGRAM_PREFIXES = ("serve.", "kv.", "tier.")
# every span of the serving loop, and the span each one nests in
PARENT = {
    "serve.schedule": "serve.iteration",
    "serve.prefill": "serve.iteration",
    "kv.write_prefill": "serve.prefill",
    "kv.ensure_tail": "serve.iteration",
    "kv.gather": "serve.iteration",
    "kv.gather_seq": "kv.gather",
    "serve.decode": "serve.iteration",
    "serve.sync": "serve.iteration",
    "serve.deliver": "serve.iteration",
    "kv.append": "serve.deliver",
    "tier.step": "serve.iteration",
    "serve.control": "serve.iteration",
}


def profiled(log_dir, fn):
    """Run ``fn`` under the profiler (spans only, no Python call
    tracing) and return the host events of the trace it wrote:
    (name, start_ns, end_ns, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(log_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events)
    return out


# ===================================================================== #
# One tracer: annotate and the recorder's spans                          #
# ===================================================================== #
def test_annotate_lands_its_integer_args_as_stats(tmp_path):
    def body():
        with annotate("kv.gather", h2d_bytes=2 ** 40, seqs=3):
            pass
    ev = [e for e in profiled(tmp_path, body) if e[0] == "kv.gather"]
    assert len(ev) == 1
    assert ev[0][3] == {"h2d_bytes": 2 ** 40, "seqs": 3}


def test_recorder_span_records_in_the_ring_and_on_the_profiler(tmp_path):
    tr = TraceRecorder(clock=iter([1.0, 3.5]).__next__)

    def body():
        with tr.span("replan.round", cat="test", epoch=4) as args:
            args["moved"] = 2
    events = profiled(tmp_path, body)
    assert [e[0] for e in events].count("replan.round") == 1
    (ev,) = tr.events
    assert (ev.name, ev.ph, ev.ts_s, ev.dur_s) == ("replan.round", "X",
                                                   1.0, 2.5)
    assert ev.args == {"epoch": 4, "moved": 2}


# ===================================================================== #
# PoolCounters: bytes and puts that cross host <-> device               #
# ===================================================================== #
def _pool(default_kind="pinned_host", pooled=False):
    spec = KVBlockSpec(n_units=1, n_attn=2, block_tokens=4, n_kv=2,
                       head_dim=8, dtype="float32")
    return PagedKVPool(8, 4, spec=spec, default_kind=default_kind,
                       pooled=pooled)


def _script(pool, kind):
    """Prefill 6 tokens (2 blocks), gather, append 3 tokens (two into
    the written tail block, one into a fresh block), migrate the first
    block to the device and back, gather again."""
    rs = np.random.RandomState(0)
    kv = jnp.asarray(rs.randn(1, 2, 6, 2, 8), jnp.float32)
    tok = jnp.asarray(rs.randn(1, 2, 2, 8), jnp.float32)
    pool.write_prefill(1, kv, kv, n_tokens=6, kind=kind)
    pool.gather_seq(1, 4)
    pool.append_token(1, tok, tok)
    pool.append_token(1, tok, tok)
    pool.alloc(1, 1, kind=kind)
    pool.append_token(1, tok, tok)
    first = pool.table[1][0]
    pool.migrate(first, "device")
    pool.migrate(first, kind)
    pool.gather_seq(1, 4)
    c = pool.counters
    return (c.h2d_bytes, c.d2h_bytes, c.h2d_puts, c.d2h_puts, c.h2d_calls,
            c.d2h_calls)


def test_pool_counts_every_host_device_crossing():
    pool = _pool()
    bn = pool.block_nbytes()
    assert bn == 2 * 1 * 2 * 4 * 2 * 8 * 4       # K and V, float32
    # one put a block (K and V are one buffer), one call a gather
    h2d = (2          # first gather: both prefill blocks, one call
           + 2        # two appends into the written tail block: in ...
           + 1        # migration to the device
           + 3)       # second gather: all three blocks, one call
    d2h = (2          # prefill writes, one call
           + 2        # ... and back out
           + 1        # append into a fresh block: out only
           + 1)       # migration back
    assert _script(pool, "pinned_host") == (h2d * bn, d2h * bn, h2d, d2h,
                                            1 + 2 + 1 + 1, 1 + 2 + 1 + 1)


def test_pool_counts_nothing_on_the_device_or_in_the_pooled_layout():
    assert _script(_pool(default_kind="device"), "device") == (0,) * 6
    assert _script(_pool(pooled=True), "pinned_host") == (0,) * 6


# ===================================================================== #
# A profiled engine run                                                  #
# ===================================================================== #
@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("llama3-8b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(tiny, n=3, prompt=12, new_tokens=6):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, ServingConfig(
        block_tokens=8, max_batch=2, max_context=32, policy="tiering08",
        num_blocks=12, fast_block_budget=2))
    rs = np.random.RandomState(0)
    for _ in range(n):
        eng.submit(rs.randint(0, cfg.vocab, (prompt,)).astype(np.int32),
                   max_new_tokens=new_tokens)
    return eng


@pytest.fixture(scope="module")
def served(tiny, tmp_path_factory):
    eng = _engine(tiny)
    eng.run()                                  # compile outside the trace
    eng = _engine(tiny)
    events = profiled(tmp_path_factory.mktemp("trace"), eng.run)
    spans = [e for e in events if e[0].startswith(PROGRAM_PREFIXES)]
    return eng, spans


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_trace_holds_every_span_nested(served):
    eng, spans = served
    names = {s[0] for s in spans}
    assert set(PARENT) | {"serve.iteration"} <= names
    iterations = [s for s in spans if s[0] == "serve.iteration"]
    assert [s[3]["step_num"] for s in iterations] == list(range(eng._step))
    for s in spans:
        if s[0] in PARENT:
            want = PARENT[s[0]]
            assert any(_within(s, p) for p in spans if p[0] == want), s
    # the iteration's phases follow one another, none inside another
    for it in iterations:
        phases = sorted(s[1:3] for s in spans
                        if PARENT.get(s[0]) == "serve.iteration"
                        and _within(s, it))
        assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))


def test_engine_spans_carry_the_counters(served):
    eng, spans = served
    final = eng._counters()
    assert final["tokens_out"] == 3 * 6
    assert final["prefill_tokens"] == 3 * 12
    assert final["kv_h2d_bytes"] > 0 and final["kv_d2h_bytes"] > 0
    assert final["kv_h2d_puts"] == 1 * final["kv_h2d_bytes"] \
        // eng.pool.block_nbytes()
    iterations = [s for s in spans if s[0] == "serve.iteration"]
    for name in ("serve.iteration", "kv.gather", "serve.decode"):
        for s in spans:
            if s[0] == name:
                assert set(COUNTERS) <= set(s[3]), s
    assert {"running", "waiting"} <= set(iterations[0][3])
    assert all(iterations[0][3][k] == 0 for k in COUNTERS)
    for k in COUNTERS:
        seen = [s[3][k] for s in iterations]
        assert seen == sorted(seen) and seen[-1] <= final[k]
    gathers = [s for s in spans if s[0] == "kv.gather"]
    assert all(s[3]["seqs"] >= 1 and s[3]["blocks"] >= s[3]["seqs"]
               for s in gathers)
    prefills = [s for s in spans if s[0] == "serve.prefill"]
    assert sorted(s[3]["rid"] for s in prefills) == [0, 1, 2]
    assert all(s[3]["tokens"] == 12 for s in prefills)


def test_a_gather_moves_each_sequence_in_one_call(served):
    """Between a ``kv.gather`` and its ``serve.decode``, one put a
    host-resident block and at most one call a sequence."""
    eng, spans = served
    gathers = sorted((s for s in spans if s[0] == "kv.gather"),
                     key=lambda s: s[1])
    decodes = sorted((s for s in spans if s[0] == "serve.decode"),
                     key=lambda s: s[1])
    assert len(gathers) == len(decodes)
    moved = 0
    for g, d in zip(gathers, decodes):
        assert g[2] <= d[1]
        puts = d[3]["kv_h2d_puts"] - g[3]["kv_h2d_puts"]
        calls = d[3]["kv_h2d_calls"] - g[3]["kv_h2d_calls"]
        assert puts <= g[3]["blocks"] and calls <= g[3]["seqs"]
        assert (calls > 0) == (puts > 0)
        assert d[3]["kv_h2d_bytes"] - g[3]["kv_h2d_bytes"] == \
            puts * eng.pool.block_nbytes()
        moved += puts
    assert moved > 0              # the pool holds 2 of 12 blocks in HBM


def test_hot_path_spans_stay_out_of_the_recorder_ring(served):
    eng, _ = served
    assert not [e.name for e in eng.tracer.events
                if e.name.startswith(PROGRAM_PREFIXES)]


def test_pool_occupancy_is_a_running_mean():
    m = ServingMetrics()
    for used, running in [(4, 1), (6, 2), (0, 0)]:
        m.on_iteration(used, running)
    assert not hasattr(m, "samples")
    assert (m.iterations, m.decode_steps) == (3, 2)
    assert m.mean_occupancy() == pytest.approx(10 / 3)
    assert m.summary()["mean_pool_blocks"] == pytest.approx(10 / 3)


# ===================================================================== #
# Program names and decode scopes                                        #
# ===================================================================== #
def test_programs_carry_their_names_and_the_decode_scopes(tiny):
    cfg, params = tiny
    eng = _engine(tiny, n=0)
    spec, B = eng.pool.spec, eng.max_batch
    kv = jax.ShapeDtypeStruct(
        (spec.n_units, spec.n_attn, B, eng.max_seq_blocks * 8, spec.n_kv,
         spec.head_dim), jnp.bfloat16)
    low = eng._decode.lower(params, np.zeros((B, 1), np.int32), kv, kv,
                            np.zeros((B,), np.int32))
    assert low.as_text().startswith("module @jit_serve_decode ")
    locs = set(re.findall(r'loc\("([^"]*)"', low.as_text(debug_info=True)))
    for scope in ("attention", "kv_write", "mlp", "head"):
        assert any(f"{scope}/" in loc for loc in locs), scope
    # the kv write is the scatter at each sequence's length
    assert any(loc.startswith("kv_write/scatter") for loc in locs)
    # the benchmark finds the attention kernel by this substring: only
    # the kernel's own program (and source file) may hold it
    parts = {p for loc in locs if not loc.endswith(".py")
             for p in loc.split("/")}
    assert {p for p in parts if "decode_attention" in p} \
        == {"jit(decode_attention)", "decode_attention"}
    pre = eng._prefill.lower(params, {"tokens": np.zeros((1, 8), np.int32)})
    assert pre.as_text().startswith("module @jit_serve_prefill ")
