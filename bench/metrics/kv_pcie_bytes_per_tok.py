"""KV bytes that crossed between host memory and the device per output
token, from the program's own counters (``PoolCounters``, counted at
each ``device_put`` that crosses: gathers, appends, migrations and
prefill writes): the change of ``kv_h2d_bytes + kv_d2h_bytes`` over
the change of ``tokens_out`` from the first to the last
``serve.iteration`` wholly inside the traced window."""
from bench import program_trace as pt


def value(trace):
    its = trace.iterations()
    if len(its) < 2:
        return None
    a, b = its[0], its[-1]
    tokens = pt.stat(b, "tokens_out") - pt.stat(a, "tokens_out")
    if tokens <= 0:
        return None
    return sum(pt.stat(b, k) - pt.stat(a, k)
               for k in ("kv_h2d_bytes", "kv_d2h_bytes")) / tokens


def read(run):
    trace = pt.of(run)
    return None if trace is None else value(trace)
