"""Serve stablelm-1.6b once on a TPU through the tiered paged-KV engine.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 4 one-chip replicas vs one replica

The one-chip run builds the full published stablelm-1.6b (24 layers,
d_model 2048) with random weights from ``--seed`` and serves 8 requests
(prompts of 512 and 256 tokens, 32 new tokens each) through
``ServingEngine``: continuous batching, the staged decode over the
``PagedKVPool``, whose blocks live in ``pinned_host`` memory beyond a
64-block fast budget.  It checks that every request finished, that KV
bytes really sat in host memory, that the decode step holds the Mosaic
kernel, and that one served decode step's logits match the plain
full-sequence forward (``models/lm.py``) of the same tokens.

``--four-chips`` runs only the multi-replica path: ``ClusterPlane`` with
4 one-chip replicas behind the headroom-distance router on the same
trace, against one replica; each request's tokens must be equal.

Timings printed here are smoke timings, not benchmark numbers.  Any
failed check exits non-zero.  The last line of a passing run is the JSON
object ``{"ok": true, "device": {...}}``; without a TPU, or without the
repository's ``src/`` beside this file, the script exits non-zero
before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ARCH = "stablelm-1.6b"
PROMPT_LENS = (512, 256)
N_REQUESTS = 8
NEW_TOKENS = 32
BLOCK_TOKENS = 16
MAX_CONTEXT = 1024
FAST_BLOCKS = 64          # well below the pool's 512 blocks
# bf16 weights and activations on both paths; the served step runs its
# attention in the Pallas kernel and the reference in chunked pure JAX,
# so they agree to a few bf16 ulps of the logit scale, not bitwise
LOGIT_RTOL = 2.0 ** -5


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_trace(cfg, seed: int):
    import numpy as np
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab, (PROMPT_LENS[i % len(PROMPT_LENS)],)
                       ).astype(np.int32) for i in range(N_REQUESTS)]


def serving_config():
    from repro.serving import ServingConfig
    return ServingConfig(block_tokens=BLOCK_TOKENS, max_batch=N_REQUESTS,
                         max_context=MAX_CONTEXT,
                         fast_block_budget=FAST_BLOCKS)


def finished_tokens(engine, rids):
    done = {r.rid: r for r in engine.sched.finished}
    return {rid: list(done[rid].out_tokens) for rid in rids if rid in done}


class DecodeProbe:
    """Wraps an engine's staged decode step: on the first call with a
    full batch it keeps the step's logits, the token sequences they
    continue, the lowered step text and the pool's KV placement."""

    def __init__(self, engine, want_batch: int):
        self.engine = engine
        self.want = want_batch
        self.step = engine._decode
        self.seen = None
        engine._decode = self

    def __call__(self, params, tokens, kv_k, kv_v, lengths):
        out = self.step(params, tokens, kv_k, kv_v, lengths)
        running = self.engine.sched.running
        if self.seen is None and len(running) == self.want:
            pool = self.engine.pool
            by_kind = {}
            for b in pool.blocks:
                if b.kv is not None:
                    kind = b.kv.sharding.memory_kind
                    by_kind[kind] = by_kind.get(kind, 0) + b.kv.nbytes
            text = self.step.lower(params, tokens, kv_k, kv_v,
                                   lengths).as_text()
            self.seen = {
                "logits": out[0],
                "seqs": [list(r.prompt) + list(r.out_tokens)
                         for r in running],
                "kv_bytes_by_kind": by_kind,
                "mosaic": "tpu_custom_call" in text,
            }
        return out


def logits_check(cfg, params, seen) -> dict:
    """The served step's logits against lm.prefill over the same tokens,
    row by row, with float32 matmuls at full precision in the reference.
    Every row must agree within ``LOGIT_RTOL`` of its logit scale and in
    its top-1 token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    ref_fwd = jax.jit(lm.prefill, static_argnums=1)
    served = np.asarray(seen["logits"])
    rows = []
    for i, seq in enumerate(seen["seqs"]):
        with jax.default_matmul_precision("highest"):
            ref, _ = ref_fwd(params, cfg, jnp.asarray([seq], jnp.int32))
        ref = np.asarray(ref[0])
        diff = float(np.max(np.abs(served[i] - ref)))
        top2 = np.sort(ref)[-2:]
        rows.append({"diff": diff,
                     "tol": LOGIT_RTOL * float(np.max(np.abs(ref))),
                     "margin": float(top2[1] - top2[0]),
                     "agree": bool(np.argmax(served[i]) == np.argmax(ref))})
    return {"rows": rows,
            "within_tol": sum(r["diff"] <= r["tol"] for r in rows),
            "agree": sum(r["agree"] for r in rows),
            "max_abs_diff": max(r["diff"] for r in rows),
            "tol": min(r["tol"] for r in rows)}


def run_one_chip(cfg, device, seed: int) -> None:
    import jax

    from repro.launch.serve import init_params
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    params = init_params(cfg, seed, device)
    jax.block_until_ready(params)
    setup_s = time.perf_counter() - t0
    engine = ServingEngine(cfg, params, serving_config())
    prompts = make_trace(cfg, seed)

    # warm-up: one request per prompt length compiles both prefills and
    # the (fixed-batch) decode step before anything is timed
    t0 = time.perf_counter()
    for p in prompts[:len(PROMPT_LENS)]:
        engine.submit(p, max_new_tokens=2)
    engine.run()
    compile_s = time.perf_counter() - t0
    print(f"smoke timing: set-up {setup_s:.3f} s (weights), "
          f"warm-up {compile_s:.3f} s (compiles)")

    probe = DecodeProbe(engine, want_batch=N_REQUESTS)
    rids = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    report = engine.run()
    wall = time.perf_counter() - t0
    out = finished_tokens(engine, rids)
    n_tok = sum(len(v) for v in out.values())
    print(f"requests finished: {len(out)}/{len(rids)}, "
          f"tokens generated: {n_tok}")
    check(len(out) == len(rids), "not every request finished")
    check(all(len(v) == NEW_TOKENS for v in out.values()),
          f"a request generated other than {NEW_TOKENS} tokens")
    rows = dict(report.per_request)
    for rid in rids:
        row = rows[rid]
        print(f"smoke timing: req{rid} prompt={int(row['prompt_tokens'])} "
              f"ttft={row['ttft_s'] * 1e3:.3f} ms "
              f"decode={row['decode_tok_s']:.3f} tok/s")
    print(f"smoke timing: served {n_tok} tokens in {wall:.3f} s")

    seen = probe.seen
    check(seen is not None, "the decode step never ran a full batch")
    kv = seen["kv_bytes_by_kind"]
    print(f"kv bytes by memory kind at a full-batch step: {kv}")
    check(kv.get("pinned_host", 0) > 0, "no KV block lived in pinned_host")
    print(f"decode step contains tpu_custom_call: "
          f"{'yes' if seen['mosaic'] else 'no'}")
    check(seen["mosaic"], "the decode step holds no Mosaic kernel")
    lc = logits_check(cfg, params, seen)
    for i, r in enumerate(lc["rows"]):
        print(f"logits row {i}: max abs diff {r['diff']:.6f} "
              f"(tolerance {r['tol']:.6f}), reference top-1 margin "
              f"{r['margin']:.6f}, top-1 "
              f"{'agrees' if r['agree'] else 'differs'}")
    n = len(lc["rows"])
    print(f"logits vs lm.prefill: {lc['within_tol']}/{n} rows within "
          f"tolerance (max abs diff {lc['max_abs_diff']:.6f}), top-1 "
          f"agrees on {lc['agree']}/{n} rows")
    check(lc["within_tol"] == n,
          "logits differ from lm.prefill beyond the bf16 tolerance")
    check(lc["agree"] == n, "top-1 disagrees with lm.prefill")


def run_four_chips(cfg, devices, seed: int) -> None:
    import jax

    from repro.cluster import ClusterPlane
    from repro.launch.serve import init_params
    from repro.serving import ServingEngine

    n = 4
    check(len(devices) >= n, f"--four-chips needs 4 devices, "
                             f"JAX has {len(devices)}")
    prompts = make_trace(cfg, seed)
    params = init_params(cfg, seed, devices[0])

    one = ServingEngine(cfg, params, serving_config())
    rids = [one.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    one.run()
    print(f"smoke timing: one replica served {len(rids)} requests in "
          f"{time.perf_counter() - t0:.3f} s (compiles included)")
    want = finished_tokens(one, rids)
    check(len(want) == len(rids), "one replica left requests unfinished")
    del one

    plane = ClusterPlane(cfg, params, serving=serving_config(),
                         n_replicas=n, router_policy="headroom-distance",
                         shard_model=False, seed=seed)
    del params               # chip 0 now holds replica 0's copy only
    for d in devices[:n]:
        stats = d.memory_stats() or {}
        print(f"{d}: bytes_in_use "
              f"{stats.get('bytes_in_use', 'not reported')}")
    sids = [plane.submit(p, NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    report = plane.run()
    print(f"smoke timing: {n} replicas served {len(sids)} requests in "
          f"{time.perf_counter() - t0:.3f} s (compiles included)")
    print(f"routed: {dict(sorted(report.routed.items()))}")
    same = 0
    for sid, rid in zip(sids, rids):
        host, r = sid.split(":")
        got = finished_tokens(plane.replicas[host].engine, [int(r)])
        same += int(got.get(int(r)) == want[rid])
    print(f"requests whose tokens equal the one-replica run: "
          f"{same}/{len(rids)}")
    check(same == len(rids), "replica tokens differ from one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica path and its "
                         "one-replica comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import place_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    place_compile_cache()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} memories="
          f"{[m.kind for m in dev.addressable_memories()]}")
    cfg = get_config(ARCH)
    print(f"model: {cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv} "
          f"vocab={cfg.vocab}")
    try:
        if args.four_chips:
            run_four_chips(cfg, devices, args.seed)
        else:
            run_one_chip(cfg, dev, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
