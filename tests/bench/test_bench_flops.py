"""The yardstick's operation and byte counts, against shapes worked
out by hand for both configurations."""
from pathlib import Path

import pytest

from bench import flops, lookup

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


@pytest.fixture(scope="module")
def stablelm():
    return lookup.load_arch(CONFIGS / "stablelm-1.6b.json")


@pytest.fixture(scope="module")
def codeqwen():
    return lookup.load_arch(CONFIGS / "codeqwen1.5-7b.json")


def test_stablelm_shapes(stablelm):
    a = stablelm
    assert (a.d_model, a.n_heads, a.n_kv, a.head_dim, a.d_ff, a.vocab,
            a.n_layers, a.rotary_dim) == (2048, 32, 32, 64, 5632, 100352,
                                          24, 16)
    # q, k, v, o: 4 x 2048 x 2048; MLP 3 x 2048 x 5632 = 51,380,224 a
    # layer; 24 layers and the 100352 x 2048 head
    assert a.matmul_params() == 51_380_224 * 24 + 205_520_896
    # one new token over 99 cached: 2 x params + 4 x 24 x 32 x 64 x 100
    assert flops.decode_flops(a, 99) == 2_877_292_544 + 19_660_800
    # 10 prompt tokens: 2 x layer params x 10, causal attention over
    # 55 query-key pairs, and the head at the last token only
    assert flops.prefill_flops(a, 10) == (24_662_507_520 + 10_813_440
                                          + 411_041_792)


def test_codeqwen_shapes(codeqwen):
    a = codeqwen
    assert (a.d_model, a.n_heads, a.n_kv, a.head_dim, a.d_ff, a.vocab,
            a.n_layers, a.rotary_dim) == (4096, 32, 4, 128, 13440, 92416,
                                          16, 128)
    # q and o 4096 x 4096, k and v 4096 x 512, MLP 3 x 4096 x 13440
    assert a.matmul_params() == 202_899_456 * 16 + 378_535_936
    f, b = flops.decode_attn_work(a, [99, 0])         # a pad row is free
    assert f == 4 * 16 * 32 * 128 * 100 == 26_214_400
    # per layer: K and V of 100 tokens x 4 heads x 128, q and out of 32
    # heads x 128, all bf16
    assert b == 16 * 2 * (2 * 100 * 4 * 128 + 2 * 32 * 128) == 3_538_944


def test_least_time_takes_the_binding_roof():
    assert flops.least_time_s(197e12, 1.0, 197e12, 819e9) == 1.0
    assert flops.least_time_s(1.0, 819e9, 197e12, 819e9) == 1.0
    assert flops.least_time_s(0, 0, 1, 1) == 0
