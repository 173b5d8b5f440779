"""Model operations of every prompt and output token processed in the
window, over the window's length times the chip's bf16 peak."""
from bench import flops, stats


def read(run):
    work = sum(flops.prefill_flops(run.arch, n) for t, _, n in run.prefills
               if run.t_open <= t <= run.t_close)
    for s in stats.window_steps(run):
        work += sum(flops.decode_flops(run.arch, int(n))
                    for n in s.lengths if n > 0)
    if not work:
        return None
    return 100.0 * work / (stats.window_s(run) * run.peak["bf16_flops"])
