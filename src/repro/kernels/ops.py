"""Public jit'd wrappers for the Pallas kernels.

The backend picks the mode: on the CPU the kernel body runs in
interpret mode (pure JAX — how the tests check it against ref.py); on a
TPU the same calls compile to Mosaic.  Any other backend is an error,
never a silent interpreter run.
"""
from __future__ import annotations

from typing import Tuple

import jax

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import fused_adam as _adam
from . import tiered_gather as _tg


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on tpu (Mosaic) or cpu "
                           f"(interpret mode), not on {backend!r}")
    return backend == "cpu"


def fused_adam(master, m, v, g, *, lr, b1, b2, eps, wd, b1c, b2c,
               block_rows: int = 512
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return _adam.fused_adam(master, m, v, g, lr=lr, b1=b1, b2=b2, eps=eps,
                            wd=wd, b1c=b1c, b2c=b2c,
                            block_rows=block_rows,
                            interpret=_interpret())


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


def decode_attention(q, k_cache, v_cache, kv_len, *,
                     block_k: int = _dec.DEF_BLOCK_K) -> jax.Array:
    return _dec.decode_attention(q, k_cache, v_cache, kv_len,
                                 block_k=block_k, interpret=_interpret())


def paged_decode_attention(q, k_pool, v_pool, block_tbl, kv_len,
                           k_new, v_new, *, block_tokens: int
                           ) -> jax.Array:
    return _tg.paged_decode_attention(q, k_pool, v_pool, block_tbl,
                                      kv_len, k_new, v_new,
                                      block_tokens=block_tokens,
                                      interpret=_interpret())


def fused_expert_ffn(x, w_gate, w_up, w_down, expert_ids, expert_wts
                     ) -> jax.Array:
    return _tg.fused_expert_ffn(x, w_gate, w_up, w_down, expert_ids,
                                expert_wts, interpret=_interpret())
