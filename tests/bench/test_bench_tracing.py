"""The trace reduction on a small synthetic trace."""
from types import SimpleNamespace as NS

import pytest

from bench import tracing


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def synthetic():
    """One device, 1000 ns window: ops busy on [100, 300] (two that
    overlap), [500, 600] and [650, 700]; a kernel op of 80 ns; host
    spans cover the gaps."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100, 150, hlo_module="jit__paged_decode"),
            ev("_decode_kernel", 200, 100, hlo_module="jit__paged_decode"),
            ev("copy.2", 500, 100, hlo_module="jit_concatenate"),
            ev("fusion.3", 650, 50, hlo_module="jit__paged_decode"),
            ev("fusion.9", 1200, 50)]),          # after the window
        NS(name="XLA Modules", events=[
            ev("jit__paged_decode(7)", 100, 200),
            ev("jit__paged_decode(7)", 640, 70),
            ev("jit_concatenate(2)", 500, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000),
        ev("bench.kv_gather", 300, 200),
        ev("bench.append", 700, 250),
        ev("PjitFunction(f)", 0, 10)])])
    return tracing.from_profile([host, device])


def test_busy_union_and_idle_share():
    tr = synthetic()
    t0, t1 = tracing.window(tr)
    assert (t0, t1) == (0, 1000)
    ops = tr.device_ops["/device:TPU:0"]
    assert tracing.busy_ns(ops, t0, t1) == 200 + 100 + 50
    s = tracing.summarize(tr)
    assert s.window_ns == 1000 and s.busy_ns == 350 and s.n_devices == 1
    assert s.top_ops[0] == ("fusion", 150 + 50)
    assert tracing.short_name("%concatenate.1 = bf16[2] concatenate(a)") \
        == "concatenate"
    assert tracing.short_name("%while = (s32[]) while(x)") == "while"


def test_union_merges_overlaps_and_clips():
    assert tracing.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == \
        [(0, 3), (5, 10)]
    assert tracing.clip([(-5, 5), (8, 20), (30, 40)], 0, 10) == \
        [(0, 5), (8, 10)]


def test_kernel_time_by_name_and_module_runs():
    tr = synthetic()
    ops = tr.device_ops["/device:TPU:0"]
    assert tracing.kernel_ns(ops, ("_decode_kernel",), 0, 1000) == 100
    # a needle may match a stat, not only the name
    assert tracing.kernel_ns(ops, ("jit_concatenate",), 0, 1000) == 100
    mods = tr.device_modules["/device:TPU:0"]
    # the decode program's runs are those in which the kernel ran
    assert tracing.module_runs(mods, ops, ("_decode_kernel",), 0,
                               1000) == [200]
    assert tracing.module_runs(mods, ops, ("jit__paged_decode",), 0,
                               1000) == [200, 70]
    assert tracing.module_runs(mods, ops, ("jit__paged_decode",), 0,
                               650) == [200]


def test_idle_gaps_are_labelled_by_the_covering_span():
    tr = synthetic()
    ops = tr.device_ops["/device:TPU:0"]
    gaps = tracing.idle_gaps(ops, 0, 1000)
    assert gaps == [(0, 100), (300, 500), (600, 650), (700, 1000)]
    label = tracing.GapLabeller(tr.spans)
    labels = [label(g) for g in gaps]
    assert labels == ["host outside any span", "bench.kv_gather",
                      "host outside any span", "bench.append"]
    s = tracing.summarize(tr)
    # idle time is summed by what the host was doing
    assert s.top_gaps == [("bench.append", 300), ("bench.kv_gather", 200),
                          ("host outside any span", 150)]


def test_a_trace_without_window_or_device_is_refused():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 10)])])
    with pytest.raises(ValueError, match="no device"):
        tracing.summarize(tracing.from_profile([host]))
    with pytest.raises(ValueError, match="bench.window"):
        tracing.window(tracing.from_profile([]))
