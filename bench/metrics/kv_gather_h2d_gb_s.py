"""Host-to-device KV bytes of the decode gathers per second of gather:
over the traced iterations, the ``kv_h2d_bytes`` counted between each
``kv.gather`` span's opening and the ``serve.decode`` that follows it,
over the ``kv.gather`` spans' summed durations (bytes a nanosecond are
GB/s).  The span covers the host's dispatch of the transfers, the
concatenation and the stack; a transfer still in flight when it closes
is waited for later, at the read-back (``serve.sync``)."""
from bench import program_trace as pt


def value(trace):
    moved = ns = 0.0
    for _, inner in trace.by_iteration():
        gathers = [s for s in inner if s.name == "kv.gather"]
        decodes = [s for s in inner if s.name == "serve.decode"]
        for g, d in zip(gathers, decodes):
            moved += pt.stat(d, "kv_h2d_bytes") - pt.stat(g, "kv_h2d_bytes")
            ns += g.dur_ns
    return moved / ns if ns else None


def read(run):
    trace = pt.of(run)
    return None if trace is None else value(trace)
