"""Median host wall time of one request's prefill (``_do_prefill``,
which ends in the argmax read-back) in the window: long prompts."""
from bench import stats


def read(run):
    return stats.percentile([dt * 1e3 for t, dt, _ in run.prefills
                             if run.t_open <= t <= run.t_close], 50)
