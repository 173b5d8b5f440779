"""Whole runs of a cell on the CPU at a small size.

Set-up, warm-up, the closed-loop window, the metrics and the check
against the reference run as on the chip (the entry point itself
refuses the CPU, so nothing here prints a result line).  The check
separates the program from its lower-precision control, and comes out
not correct when the timed path is broken underneath."""
import dataclasses
import functools
import importlib
import json
import time

import jax.numpy as jnp
import pytest

from bench import harness
from repro.serving import engine as engine_mod

CELL = "codeqwen1.5-7b.code.kv-host"
HBM = "stablelm-1.6b.chat.kv-hbm"
LIMIT = 0.05


def small_cell(name: str = CELL, limit: float = LIMIT) -> harness.Cell:
    """A cell of the benchmark cut to a size the CPU runs in seconds:
    the same files, mix shapes and serving settings, at small widths
    and lengths."""
    full = harness.Cell.load(harness.load_benchmark(), name)
    a = full.arch
    sizes = dict(d_model=64, n_heads=4, n_kv=4 if a.n_kv == a.n_heads else 2,
                 head_dim=16, d_ff=128, vocab=512, n_layers=2)
    arch = dataclasses.replace(
        a, **sizes, overrides=a.overrides + tuple(sizes.items()))
    mix = dataclasses.replace(
        full.mix,
        prompt=dict(full.mix.prompt, median=40, min=16, max=64,
                    round_up_to=[32, 64]),
        output=dict(full.mix.output, median=8, min=4, max=16))
    static = full.serving["policy"] == "static"
    serving = dict(full.serving, max_context=80, num_blocks=40,
                   fast_block_budget=40 if static else 5)
    return dataclasses.replace(
        full, arch=arch, mix=mix, serving=serving,
        check={"max_requests": 16, "limits": {"max_logit_gap": limit}})


def run(tmp_path, trace: bool, name: str = CELL, seed: int = 2 ** 31 + 7):
    lines = []
    out = harness.run_cell(harness.load_benchmark(), name, seed, 2.0,
                           trace, time.perf_counter(), allow_cpu=True,
                           cell=small_cell(name), log=lines.append,
                           trace_dir=tmp_path / "trace")
    return out, lines


def no_result_line(lines):
    for ln in lines:
        try:
            json.loads(ln)
        except ValueError:
            continue
        raise AssertionError(f"a log line parses as JSON: {ln}")


def test_a_window_is_served_checked_and_reported(tmp_path):
    out, lines = run(tmp_path, trace=False)
    no_result_line(lines)
    assert "compiles in window: 0" in lines
    assert "preemptions in window: 0" in lines
    assert out["correct"] is True
    assert out["attempted"] >= 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    c = out["checks"]["max_logit_gap"]
    assert 0 <= c["value"] <= c["limit"]


def test_the_traced_run_reads_the_host_side_layers(tmp_path):
    out, lines = run(tmp_path, trace=True)
    no_result_line(lines)
    assert out["correct"] is True
    # the CPU has no device planes: the device readers find nothing
    assert set(out["metrics"]) == {"output_tok_s.unbounded",
                                   "prefill_ms_p50.long",
                                   "kv_gather_ms_per_step",
                                   "kv_host_bytes_per_tok", "mfu"}
    assert out["metrics"]["kv_host_bytes_per_tok"]["value"] > 0


def test_the_hbm_control_moves_no_kv_across(tmp_path):
    out, _ = run(tmp_path, trace=True, name=HBM)
    assert out["correct"] is True
    assert out["metrics"]["kv_host_bytes_per_tok"]["value"] == 0


# The check separates the program from its lower-precision control.
#
# On both cells (three seeds and two), a small cell's served tokens lie within
# the limit of the float32 reference's best.  Put in the program's place
# by the calibration's control pass, the reference computed in float8
# picks tokens whose gap is over the limit and at least three times the
# program's, and the run's own comparison comes out not correct.
@pytest.mark.parametrize("name,seed", [(CELL, 11), (CELL, 12),
                                       (CELL, 2 ** 31 + 13),
                                       (HBM, 14), (HBM, 15)])
def test_program_within_and_control_beyond_the_limit(name, seed,
                                                      monkeypatch):
    monkeypatch.syspath_prepend(str(harness.BENCH))
    calibrate = importlib.import_module("calibrate")
    monkeypatch.setattr(harness, "reference_gaps", harness.reference_gaps)
    seen = calibrate.control_pass(harness, LIMIT)
    out = harness.run_cell(harness.load_benchmark(), name, seed, 2.0,
                           False, time.perf_counter(), allow_cpu=True,
                           cell=small_cell(name, limit=LIMIT),
                           log=lambda *_: None)
    assert seen["program_correct"] is True and seen["tokens"] > 0
    assert out["correct"] is False
    ctl = out["checks"]["max_logit_gap"]["value"]
    assert seen["program"] <= LIMIT < ctl
    assert ctl >= 3 * max(seen["program"], 1e-3)


# A run whose timed path is broken underneath comes out not correct.
#
# The harness's look for a chip is skipped (``allow_cpu``) and the rest
# of the run is driven as it is on the chip, with the program's decode
# step replaced by a broken one before the engine is built.
def token_altered(orig, *a):
    """Every row's next token is forced to token 0."""
    logits, k, v = orig(*a)
    return logits.at[:, 0].set(logits.max(-1) + 1.0), k, v


def kv_dropped(orig, *a):
    """The new token's keys and values are never written."""
    logits, k, v = orig(*a)
    return logits, jnp.zeros_like(k), jnp.zeros_like(v)


@pytest.mark.parametrize("fault", [token_altered, kv_dropped])
def test_a_broken_step_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(engine_mod, "_paged_decode",
                        functools.partial(fault, engine_mod._paged_decode))
    out = harness.run_cell(harness.load_benchmark(), CELL, 5, 2.0, False,
                           time.perf_counter(), allow_cpu=True,
                           cell=small_cell(), log=lambda *_: None)
    c = out["checks"]["max_logit_gap"]
    assert out["correct"] is False and c["value"] > c["limit"]
