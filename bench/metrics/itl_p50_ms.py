"""Median gap between two consecutive output tokens of one request,
over every such gap of every request that lies wholly in the window."""
from bench import stats


def read(run):
    return stats.percentile([(b - a) * 1e3 for r in run.records
                             for a, b in zip(r.times, r.times[1:])
                             if run.t_open <= a and b <= run.t_close], 50)
