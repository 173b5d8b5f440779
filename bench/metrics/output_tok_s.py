"""Output tokens handed over in the window, per second of the window."""
from bench import stats


def read(run):
    n = stats.tokens_in_window(run)
    return n / stats.window_s(run) if n else None
