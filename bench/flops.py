"""Operations and bytes the served work needs, from the shapes alone.

These are the yardstick's own functions: a PR may change how the
program computes, not what the work costs.  A multiply-add counts as
two operations.  Padding is never work.  What a token costs depends on
the architecture, so each count is the one of the family that made the
arch (``bench/families/<family>.py``); the roofline's bound is common.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench.lookup import family_of


def prefill_flops(arch, n_tokens: int) -> int:
    """A prompt of ``n_tokens``, with the LM head at its last position."""
    return family_of(arch).prefill_flops(arch, n_tokens)


def decode_flops(arch, cached: int) -> int:
    """One new token over ``cached`` tokens already in the cache."""
    return family_of(arch).decode_flops(arch, cached)


def decode_attn_work(arch, lengths: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) that decode attention needs for one step over
    live sequences of ``lengths`` cached tokens (pad rows, length 0,
    are no work)."""
    return family_of(arch).decode_attn_work(arch, lengths)


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bw: float) -> float:
    """The roofline's bound: the larger of compute and memory time."""
    return max(flops / peak_flops, nbytes / peak_bw)
