"""The dense family: the two configurations' weights, reference logits
and work counts as recorded before the dense code became a family
(``dense_identity.json``), and a dense configuration that publishes its
``head_dim``."""
import dataclasses
import hashlib
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import flops, harness, lookup, weights
from test_bench_runs import small_cell

HERE = Path(__file__).resolve().parent
CONFIGS = HERE.parents[1] / "bench" / "configs"
GOLDEN = json.loads((HERE / "dense_identity.json").read_text())
NAMES = sorted(GOLDEN["configs"])


def small(arch):
    """The recorded size: the configuration's own kind of heads and
    norm, at widths the CPU runs in a second."""
    sizes = dict(d_model=64, n_heads=4,
                 n_kv=4 if arch.n_kv == arch.n_heads else 2,
                 head_dim=16, d_ff=128, vocab=512, n_layers=2)
    return dataclasses.replace(
        arch, **sizes, overrides=arch.overrides + tuple(sizes.items()))


def digest(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()


def params_of(arch):
    return weights.make_params(arch.program_config(), GOLDEN["seed"],
                               jax.devices()[0],
                               lookup.family_of(arch).fill)


@pytest.mark.parametrize("name", NAMES)
def test_dense_weights_are_the_recorded_ones(name):
    arch = small(lookup.load_arch(CONFIGS / f"{name}.json"))
    leaves = jax.tree_util.tree_flatten_with_path(params_of(arch))[0]
    got = {jax.tree_util.keystr(p): digest(v) for p, v in leaves}
    assert got == GOLDEN["configs"][name]["weights"]


@pytest.mark.parametrize("mode", [None, "fp8"])
@pytest.mark.parametrize("name", NAMES)
def test_dense_reference_logits_are_the_recorded_ones(name, mode):
    arch = small(lookup.load_arch(CONFIGS / f"{name}.json"))
    seq = np.random.default_rng(GOLDEN["seed"]).integers(
        0, arch.vocab, 40, dtype=np.int32)
    x = lookup.family_of(arch).logits(arch, params_of(arch), seq,
                                       np.arange(32, 40), 256, 16,
                                       mode=mode)
    want = GOLDEN["configs"][name]["logits"][str(mode)]
    assert [float(v) for v in x[0, :4]] == want["first"]
    assert digest(x) == want["sha256"]


@pytest.mark.parametrize("size", ["full", "small"])
@pytest.mark.parametrize("name", NAMES)
def test_dense_work_counts_are_the_recorded_ones(name, size):
    arch = lookup.load_arch(CONFIGS / f"{name}.json")
    arch = small(arch) if size == "small" else arch
    want = GOLDEN["configs"][name][size]
    assert {str(n): flops.prefill_flops(arch, n)
            for n in GOLDEN["prefill"]} == want["prefill_flops"]
    assert {str(n): flops.decode_flops(arch, n)
            for n in GOLDEN["decode"]} == want["decode_flops"]
    assert [[ls, *flops.decode_attn_work(arch, ls)]
            for ls in GOLDEN["attn"]] == want["decode_attn_work"]


def test_a_published_head_dim_is_run_and_served(tmp_path):
    """Four heads of 32 over a width of 64: ``hidden_size / heads``
    would give 16."""
    d = json.loads((CONFIGS / "codeqwen1.5-7b.json").read_text())
    sizes = dict(d_model=64, n_heads=4, n_kv=2, head_dim=32, d_ff=128,
                 vocab=512, n_layers=2)
    d.update(name="wide-heads", hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, intermediate_size=128,
             vocab_size=512, num_hidden_layers=2,
             overrides={k: {"value": v, "why": "a test"}
                        for k, v in sizes.items()})
    path = tmp_path / "wide-heads.json"
    path.write_text(json.dumps(d))
    arch = lookup.load_arch(path)
    assert arch.head_dim == 32 != arch.d_model // arch.n_heads
    cfg = arch.program_config()
    assert cfg.head_dim == 32 and cfg.d_model == 64
    params = params_of(arch)
    assert params["units"]["layers"][0]["attn"]["wq"].shape[1:] == (64, 128)
    # the work counts take the published width: K and V of 6 tokens,
    # 2 heads of 32, and q and out of 4 heads of 32, in 2 layers, bf16
    assert flops.decode_attn_work(arch, [5]) == (
        4 * 2 * 4 * 32 * 6, 2 * 2 * (2 * 6 * 2 * 32 + 2 * 4 * 32))
    cell = dataclasses.replace(small_cell(), arch=arch)
    out = harness.run_cell(harness.load_benchmark(), cell.name,
                           2 ** 31 + 21, 2.0, False, time.perf_counter(),
                           allow_cpu=True, cell=cell, log=lambda *_: None)
    assert out["correct"] is True
    assert out["checks"]["max_logit_gap"]["value"] <= \
        out["checks"]["max_logit_gap"]["limit"]
