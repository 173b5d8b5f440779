"""Operations and bytes the served work needs, from the shapes alone.

These are the yardstick's own functions: a PR may change how the
program computes, not what the work costs.  A multiply-add counts as
two operations.  Padding is never work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def attn_flops(arch, n_keys: int) -> int:
    """Scores and weighted values of one query over ``n_keys`` keys, in
    every layer."""
    return 4 * arch.n_layers * arch.n_heads * arch.head_dim * n_keys


def prefill_flops(arch, n_tokens: int) -> int:
    """A prompt of ``n_tokens`` through every layer, causal attention,
    and the LM head at the last position only."""
    layers = arch.matmul_params() - arch.vocab * arch.d_model
    causal_keys = n_tokens * (n_tokens + 1) // 2
    return (2 * layers * n_tokens + attn_flops(arch, causal_keys)
            + 2 * arch.vocab * arch.d_model)


def decode_flops(arch, cached: int) -> int:
    """One new token over ``cached`` tokens already in the cache."""
    return 2 * arch.matmul_params() + attn_flops(arch, cached + 1)


def decode_attn_work(arch, lengths: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) that decode attention needs for one step: in
    every layer, per live sequence, the new query against its
    ``len + 1`` keys and values, reading those keys and values once and
    the query, writing the output.  Pad rows (length 0) are no work."""
    flops = 0
    nbytes = 0
    for n in lengths:
        if n <= 0:
            continue
        keys = n + 1
        flops += attn_flops(arch, keys)
        nbytes += arch.n_layers * BF16 * (
            2 * keys * arch.n_kv * arch.head_dim
            + 2 * arch.n_heads * arch.head_dim)
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bw: float) -> float:
    """The roofline's bound: the larger of compute and memory time."""
    return max(flops / peak_flops, nbytes / peak_bw)
