"""Host milliseconds per decode iteration from the first ``gather_seq``
to the decode call: every sequence's gather, padding and the stack."""
from bench import stats


def read(run):
    steps = stats.window_steps(run)
    if not steps:
        return None
    return 1e3 * sum(s.gather_s for s in steps) / len(steps)
