"""Device milliseconds of one execution of the jitted decode program
(the program runs that hold the decode attention kernel), from the
profiler trace."""
from bench import tracing

KERNELS = ("decode_attention", "paged_decode_attention")


def read(run):
    if run.trace is None:
        return None
    t0, t1 = tracing.window(run.trace)
    runs = [d for dev, mods in run.trace.device_modules.items()
            for d in tracing.module_runs(mods, run.trace.device_ops[dev],
                                         KERNELS, t0, t1)]
    return sum(runs) / len(runs) / 1e6 if runs else None
