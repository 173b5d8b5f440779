"""The program's spans and counters as the benchmark reads them: the
three readers and the attribution tables on a small synthetic trace,
the guard against a trace that is not the run's own, and whole traced
runs on the CPU at a small size."""
import time
from types import SimpleNamespace as NS

import pytest

from bench import harness, tracing
from bench import program_trace as pt
from test_bench_runs import CELL, HBM, small_cell

NEW = ("kv_pcie_bytes_per_tok", "kv_gather_h2d_gb_s",
       "kv_append_ms_per_step")


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def counters(tokens, h2d, d2h):
    return dict(tokens_out=tokens, prefill_tokens=0, kv_h2d_bytes=h2d,
                kv_d2h_bytes=d2h, kv_h2d_puts=0, kv_d2h_puts=0)


def synthetic_planes(program=True):
    """Window [1000, 11000] ns.  Iterations: [500, 1500] and
    [10800, 11500] straddle the window's edges; [2000, 5000] and
    [5000, 9000] decode; [9000, 10500] only prefills.  One device,
    busy on [1000, 2100], [2600, 3500] (one run of the decode program)
    and [5600, 6000]."""
    spans = [
        ev("serve.iteration", 500, 1500, **counters(0, 0, 0)),
        ev("serve.iteration", 2000, 5000, **counters(10, 1000, 200)),
        ev("kv.gather", 2100, 2600, **counters(10, 1000, 200)),
        ev("kv.gather_seq", 2150, 2300, rid=1, blocks=3),
        ev("serve.decode", 2600, 2700, **counters(10, 1500, 200)),
        ev("serve.sync", 2700, 4000),
        ev("serve.deliver", 4000, 4800),
        ev("kv.append", 4100, 4300),
        ev("kv.append", 4400, 4500),
        ev("serve.iteration", 5000, 9000, **counters(18, 2000, 600)),
        ev("kv.gather", 5100, 5600, **counters(18, 2000, 600)),
        ev("serve.decode", 5600, 5700, **counters(18, 3000, 600)),
        ev("serve.deliver", 6000, 8000),
        ev("kv.append", 6100, 6400),
        ev("serve.iteration", 9000, 10500, **counters(26, 3500, 900)),
        ev("serve.prefill", 9100, 10000, rid=4, tokens=512),
        ev("serve.iteration", 10800, 11500, **counters(30, 4000, 900)),
    ]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 11000), ev("bench.kv_gather", 2150, 2650),
        ev("PjitFunction(f)", 0, 10)] + (spans if program else []))])
    # as on the chip, an op's own stats hold no source path: that is in
    # its metadata (``xspace``)
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("%fusion.9 = fusion(a)", 1000, 2100),
            ev("%while = while(b)", 2600, 3400),
            ev("%fusion.1 = fusion(c)", 2600, 2900),
            ev("%decode_attention = custom-call(d)", 2900, 3100),
            ev("%scatter.2 = scatter(e)", 3100, 3200),
            ev("%fusion.3 = fusion(f)", 3200, 3350),
            ev("%fusion.4 = fusion(g)", 3400, 3500),
            ev("%copy.1 = copy(h)", 5600, 6000)]),
        NS(name="XLA Modules", events=[
            ev("jit_serve_prefill(3)", 1000, 2100),
            ev("jit_serve_decode(5)", 2600, 3500)])])
    return [host, device]


# the XSpace protobuf's wire format, for the few fields read
def varint(n):
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*fields):
    """A message of (field number, int | bytes | str) fields."""
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += varint(no << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(no << 3 | 2) + varint(len(v)) + v
    return out


def xspace():
    """The synthetic device's op metadata: each op's source path in a
    ``tf_op`` stat, a string value or a reference to a stat name; a
    host plane whose metadata is not read."""
    scope = "jit(serve_decode)/while/body/closed_call/"
    paths = {"%fusion.9 = fusion(a)": "jit(serve_prefill)/dot",
             "%while = while(b)": "jit(serve_decode)/while",
             "%fusion.1 = fusion(c)": scope + "attention/dot",
             "%decode_attention = custom-call(d)":
                 scope + "attention/jit(decode_attention)/pallas_call",
             "%scatter.2 = scatter(e)": scope + "kv_write/scatter",
             "%fusion.4 = fusion(g)": "jit(serve_decode)/head/dot"}
    events = [msg((1, i + 1), (2, msg((1, i + 1), (2, name),
                                      (5, msg((1, 1), (5, path))))))
              for i, (name, path) in enumerate(paths.items())]
    # by reference, under a display name, with a stat of another type
    events.append(msg((1, 50), (2, msg(
        (1, 50), (2, "%fusion.3 = fusion(f)"), (4, "fusion.3"),
        (5, msg((1, 2), (3, 7))), (5, msg((1, 1), (7, 3)))))))
    stats = [msg((1, 1), (2, msg((1, 1), (2, "tf_op")))),
             msg((1, 2), (2, msg((1, 2), (2, "flops")))),
             msg((1, 3), (2, msg((1, 3), (2, scope + "mlp/dot"))))]
    device = msg((1, 7), (2, "/device:TPU:0"),
                 (3, msg((2, "XLA Ops"), (4, msg((1, 1), (3, 5))))),
                 *[(4, e) for e in events], *[(5, st) for st in stats])
    host = msg((2, "/host:CPU"), (4, msg((1, 1), (2, msg(
        (1, 1), (2, "python"), (5, msg((1, 1), (5, "mlp/x"))))))))
    return msg((1, host), (1, device))


def traced():
    trace = pt.from_profile(synthetic_planes())
    trace.op_paths = pt.op_paths(xspace())
    return trace


def readers():
    return {n: harness.load_reader(n) for n in NEW}


def test_readers_on_a_synthetic_trace():
    trace = pt.from_profile(synthetic_planes())
    assert trace.window == (1000, 11000)
    assert [(s.start_ns, s.end_ns) for s in trace.iterations()] == \
        [(2000, 5000), (5000, 9000), (9000, 10500)]
    r = readers()
    # first to last iteration inside: (3500 + 900 - 1000 - 200) bytes
    # over 26 - 10 tokens
    assert r["kv_pcie_bytes_per_tok"].value(trace) == 3200 / 16
    # (500 + 1000) bytes over two gathers of 500 ns: 1.5 bytes a ns
    assert r["kv_gather_h2d_gb_s"].value(trace) == 1.5
    # appends of 200 + 100 and 300 ns over the two decoding iterations
    assert r["kv_append_ms_per_step"].value(trace) == 600 / 2 / 1e6


def test_idle_by_innermost_program_span():
    idle = pt.idle_by_leaf_span(pt.from_profile(synthetic_planes()))
    want = {"kv.gather": 850, "kv.gather_seq": 150, "serve.sync": 500,
            "serve.deliver": 2200, "kv.append": 600,
            "serve.iteration": 2100, "serve.prefill": 900, pt.OUTSIDE: 300}
    assert idle == pytest.approx({k: ns / 1e9 for k, ns in want.items()})
    # the idle time is the window less the device's busy union
    assert sum(idle.values()) == pytest.approx(7600 / 1e9)


def test_op_paths_from_the_wire_format():
    paths = pt.op_paths(xspace())
    assert list(paths) == ["/device:TPU:0"]
    ops = paths["/device:TPU:0"]
    assert ops["%scatter.2 = scatter(e)"] == \
        "jit(serve_decode)/while/body/closed_call/kv_write/scatter"
    # a referenced value, reached under the name and the display name;
    # a stat other than tf_op is not a path
    assert ops["fusion.3"] == ops["%fusion.3 = fusion(f)"] == \
        "jit(serve_decode)/while/body/closed_call/mlp/dot"
    assert pt.scope_of(ops["fusion.3"]) == "mlp"
    assert pt.scope_of("jit(serve_decode)/while") == "other"


def test_decode_device_time_by_scope():
    runs, scopes = pt.decode_time_by_scope(traced())
    assert runs == 1
    # the while's own time is what its body's ops leave: 50 ns
    want = {"attention": 300 + 200, "kv_write": 100, "mlp": 150,
            "head": 100, "other": 50}
    assert scopes == pytest.approx({k: ns / 1e9 for k, ns in want.items()})


def test_leaf_segments_label_self_time():
    spans = [tracing.Event(n, a, b - a)
             for n, a, b in [("a", 0, 10), ("b", 2, 4), ("c", 12, 14)]]
    assert pt.leaf_segments(spans, 0, 16) == [
        (0, 2, "a"), (2, 4, "b"), (4, 10, "a"), (10, 12, pt.OUTSIDE),
        (12, 14, "c"), (14, 16, pt.OUTSIDE)]


@pytest.fixture
def on_disk(tmp_path, monkeypatch):
    """A trace file under a stand-in for ``harness.TRACE_DIR`` whose
    contents are the synthetic trace."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    path = tmp_path / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    pt._load_once.cache_clear()

    def use(planes):
        monkeypatch.setattr(pt, "load",
                            lambda p: pt.from_profile(planes))
    yield use
    pt._load_once.cache_clear()


def run_with_window(start, end):
    return NS(trace=tracing.Trace({}, {}, [tracing.Event(
        "bench.window", start, end - start)]))


def test_readers_read_only_the_runs_own_trace(on_disk):
    on_disk(synthetic_planes())
    r = readers()
    own = run_with_window(1000, 11000)
    assert r["kv_pcie_bytes_per_tok"].read(own) == 200
    assert r["kv_gather_h2d_gb_s"].read(own) == 1.5
    assert r["kv_append_ms_per_step"].read(own) == 3e-4
    stale = run_with_window(1000, 10999)
    untraced = NS(trace=None)
    for reader in r.values():
        assert reader.read(stale) is None
        assert reader.read(untraced) is None


def test_a_program_without_spans_gives_nothing(on_disk):
    """An older program, as the benchmark meets it on a parent commit:
    the window is the run's own, but no program span is there."""
    on_disk(synthetic_planes(program=False))
    own = run_with_window(1000, 11000)
    assert all(reader.read(own) is None for reader in readers().values())
    trace = pt.from_profile(synthetic_planes(program=False))
    assert pt.idle_by_leaf_span(trace) == pytest.approx({pt.OUTSIDE:
                                                         7600 / 1e9})


def test_the_command_refuses_a_directory_without_a_trace(tmp_path,
                                                         capsys):
    assert pt.main([str(tmp_path)]) == 1
    assert pt.main([]) == 2
    assert "usage" in capsys.readouterr().err


# Whole traced runs on the CPU at a small size: the run's own trace is
# read, and the readings follow the cell's KV placement.
def traced_run(tmp_path, monkeypatch, name):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    return harness.run_cell(harness.load_benchmark(), name, 2 ** 31 + 9,
                            2.0, True, time.perf_counter(), allow_cpu=True,
                            cell=small_cell(name), log=lambda *_: None,
                            trace_dir=tmp_path / "trace")


def test_the_host_kv_cell_reads_transfers(tmp_path, monkeypatch):
    out = traced_run(tmp_path, monkeypatch, CELL)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["kv_pcie_bytes_per_tok"] > 0
    assert m["kv_gather_h2d_gb_s"] > 0
    assert m["kv_append_ms_per_step"] > 0
    lines = pt.report(pt.load(pt.newest(tmp_path / "trace")))
    assert lines[0].startswith("window ")
    assert lines[-3:] == [f"{n} {m[n]!r}" for n in NEW]


def test_the_hbm_cell_moves_nothing_across(tmp_path, monkeypatch):
    out = traced_run(tmp_path, monkeypatch, HBM)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["kv_pcie_bytes_per_tok"] == 0
    assert m["kv_append_ms_per_step"] > 0
    assert "kv_gather_h2d_gb_s" not in m       # not a metric of this cell
