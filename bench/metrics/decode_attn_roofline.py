"""The least time the traced decode steps' attention needs (live keys
and values of ``len + 1`` tokens per sequence, the query and the
output; never the padding), over the device time of the decode
attention kernel, whichever of the two kernels runs."""
from bench import flops, tracing

KERNELS = ("decode_attention", "paged_decode_attention")


def read(run):
    if run.trace is None:
        return None
    t0, t1 = tracing.window(run.trace)
    ns = sum(tracing.kernel_ns(ops, KERNELS, t0, t1)
             for ops in run.trace.device_ops.values())
    a, b = run.traced_steps
    if not ns or b <= a:
        return None
    least = 0.0
    for s in run.steps[a:b]:
        f, by = flops.decode_attn_work(run.arch, [int(n) for n in s.lengths])
        least += flops.least_time_s(f, by, run.peak["bf16_flops"],
                                    run.peak["hbm_bytes_s"])
    return 100.0 * least / (ns / len(run.trace.device_ops) / 1e9)
