"""Window metrics on a synthetic token timeline with a stall."""
from types import SimpleNamespace as NS

import pytest

from bench import harness


def timeline():
    """Window [0, 10] s.  Client A: sent at 0, first token at 0.5, then
    a token every 0.1 s, but one gap of 2 s (a stall) in the middle.
    Client B: sent at 1, first token at 1.2, tokens every 0.1 s.  One
    token before the window and one after it do not count."""
    a = [0.5 + 0.1 * i for i in range(20)]
    a = a + [a[-1] + 2.0 + 0.1 * i for i in range(20)]
    b = [1.2 + 0.1 * i for i in range(40)]
    recs = [NS(t_send=0.0, times=[-0.5] + a),
            NS(t_send=1.0, times=b + [10.5])]
    return NS(t_open=0.0, t_close=10.0, records=recs, setup_s=12.5)


def metric(name, run):
    return harness.load_reader(name).read(run)


def test_output_tokens_per_second_counts_only_the_window():
    assert metric("output_tok_s", timeline()) == pytest.approx(80 / 10.0)


def test_a_stall_lowers_the_rate_by_its_missing_tokens():
    run = timeline()
    # B sends a token every 0.1 s up to 9.9 s; then it stalls for 2.05 s
    # after its 20th token, and the 20 tokens it would have made in that
    # time are missing from the window
    steady = [1.2 + 0.1 * i for i in range(88)]
    run.records[1].times = steady
    assert metric("output_tok_s", run) == pytest.approx((40 + 88) / 10.0)
    run.records[1].times = steady[:20] + [t + 2.05 for t in steady[20:]]
    assert metric("output_tok_s", run) == pytest.approx((40 + 68) / 10.0)


def test_prefill_median_over_prefills_in_the_window():
    run = timeline()
    run.prefills = [(-1.0, 9.0, 128), (1.0, 0.2, 128), (2.0, 0.4, 1024),
                    (3.0, 0.3, 512), (10.5, 5.0, 256)]
    assert metric("prefill_ms_p50.long", run) == pytest.approx(300.0)


def test_setup_and_empty_windows():
    run = timeline()
    assert metric("setup_s", run) == 12.5
    empty = NS(t_open=0.0, t_close=10.0, records=[], steps=[],
               prefills=[], setup_s=1.0)
    for name in ("output_tok_s", "kv_gather_ms_per_step",
                 "prefill_ms_p50.long", "kv_host_bytes_per_tok"):
        assert metric(name, empty) is None


def test_itl_median_counts_gaps_wholly_inside_the_window():
    run = timeline()
    # 77 gaps of 0.1 s and the 2 s stall: the median is a plain gap
    assert metric("itl_p50_ms", run) == pytest.approx(100.0)
    run.records = [NS(times=[-1.0, 0.5, 0.8, 1.0, 10.2])]
    # 300 and 200 ms lie inside; 1500 and 9200 ms cross an edge
    assert metric("itl_p50_ms", run) == pytest.approx(250.0)
    run.records = [NS(times=[0.5]), NS(times=[9.5, 10.5])]
    assert metric("itl_p50_ms", run) is None


def test_the_unbounded_rate_reads_as_the_end_to_end_one():
    run = timeline()
    assert metric("output_tok_s.unbounded", run) == \
        metric("output_tok_s", run)
