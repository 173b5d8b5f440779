"""Traffic from a mix file and a seed."""
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[2] / "bench" / "traffic"
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_same_seed_same_requests(mix):
    m = loadgen.Mix.load(TRAFFIC / f"{mix}.json")
    a = loadgen.ClosedLoop(m, BIG_SEED, 1000)
    b = loadgen.ClosedLoop(m, BIG_SEED, 1000)
    for c in (0, 3, 7, 0, 0):
        (sa, pa), (sb, pb) = a.next_request(c), b.next_request(c)
        assert sa == sb and np.array_equal(pa, pb)
        assert pa.dtype == np.int32 and pa.min() >= 0 and pa.max() < 1000
        assert len(pa) == sa.prompt_len


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_every_seed_offers_the_same_work(mix):
    m = loadgen.Mix.load(TRAFFIC / f"{mix}.json")
    a = loadgen.ClosedLoop(m, 1, 1000)
    b = loadgen.ClosedLoop(m, BIG_SEED, 1000)
    assert a.plan == b.plan
    (sa, pa), (sb, pb) = a.next_request(2), b.next_request(2)
    assert sa == sb and not np.array_equal(pa, pb)
    for r in range(m.requests_per_client):
        # each round gives the clients one length from every stratum
        assert sorted(a.plan[c][r].prompt_len for c in range(m.clients)) \
            == loadgen.stratified_lengths(m.prompt, m.clients,
                                          (0.5 + r * loadgen.GOLDEN) % 1.0)
    lens = [s.prompt_len for c in a.plan for s in c]
    assert set(lens) <= set(m.prompt_lengths)
    outs = [s.max_new_tokens for c in a.plan for s in c]
    assert min(outs) >= m.output["min"] and max(outs) <= m.output["max"]


def test_lengths_follow_the_mix():
    m = loadgen.Mix.load(TRAFFIC / "chat.json")
    lens = [s.prompt_len for c in loadgen.schedule(m) for s in c]
    # half the drawn lengths lie at or below the 1024 median, and
    # rounding up keeps them there
    assert sum(n <= 1024 for n in lens) / len(lens) == pytest.approx(0.5)
    assert min(lens) >= 128 and max(lens) == 1280


def test_rounding_up_to_the_allowed_lengths():
    allowed = [128, 256, 512, 768]
    assert loadgen.round_up(1, allowed) == 128
    assert loadgen.round_up(128, allowed) == 128
    assert loadgen.round_up(128.5, allowed) == 256
    assert loadgen.round_up(700, allowed) == 768
    with pytest.raises(ValueError):
        loadgen.round_up(769, allowed)
    d = {"median": 100, "sigma": 1.0, "min": 10, "max": 500,
         "round_up_to": [64, 128, 500]}
    assert loadgen.stratified_lengths(d, 4, 0.5) == [64, 128, 500, 500]


def test_a_client_keeps_its_prompts_whatever_the_others_do():
    m = loadgen.Mix.load(TRAFFIC / "chat.json")
    a = loadgen.ClosedLoop(m, 9, 500)
    b = loadgen.ClosedLoop(m, 9, 500)
    for _ in range(5):
        a.next_request(1)
    a3 = [a.next_request(3)[1] for _ in range(3)]
    b3 = [b.next_request(3)[1] for _ in range(3)]
    assert all(np.array_equal(x, y) for x, y in zip(a3, b3))
