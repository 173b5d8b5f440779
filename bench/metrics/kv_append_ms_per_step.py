"""Host milliseconds of the pool's ``append_token`` calls (``kv.append``
spans) per decode iteration, over the traced iterations that decode."""
from bench import program_trace as pt


def value(trace):
    total, steps = 0.0, 0
    for _, inner in trace.by_iteration():
        if any(s.name == "serve.decode" for s in inner):
            steps += 1
            total += sum(s.dur_ns for s in inner if s.name == "kv.append")
    return total / steps / 1e6 if steps else None


def read(run):
    trace = pt.of(run)
    return None if trace is None else value(trace)
