"""Jit'd public wrappers around the Pallas kernels.

The kernels compile natively on a TPU backend and run in interpret mode
(the kernel body executes in Python) on any other; the choice is made per
call, when the caller traces, from the backend that call runs on.
``ref.py`` holds the pure-jnp oracles used by tests and by the engine's
portable fallback path.
"""
from __future__ import annotations

import jax

from .paged_attention import paged_attention as _paged
from .paged_prefill_attention import \
    paged_prefill_attention as _paged_prefill
from .prefill_attention import prefill_attention as _prefill
from .ref import (ref_paged_attention, ref_paged_prefill_attention,
                  ref_prefill_attention)


def interpret_mode() -> bool:
    """True where the kernels would run interpreted: any non-TPU backend."""
    return jax.default_backend() != "tpu"


def prefill_attention(q, k, v, *, q_start=0, window=0, softcap=0.0,
                      use_kernel=True):
    if not use_kernel:
        return ref_prefill_attention(q, k, v, q_start=q_start, window=window,
                                     softcap=softcap)
    return _prefill(q, k, v, q_start=q_start, window=window, softcap=softcap,
                    interpret=interpret_mode())


def paged_attention(q, k_pages, v_pages, block_table, lengths, *, softcap=0.0,
                    use_kernel=True):
    if not use_kernel:
        return ref_paged_attention(q, k_pages, v_pages, block_table, lengths,
                                   softcap=softcap)
    return _paged(q, k_pages, v_pages, block_table, lengths, softcap=softcap,
                  interpret=interpret_mode())


def paged_prefill_attention(q, k_pages, v_pages, block_table, q_start,
                            new_lens, *, softcap=0.0, use_kernel=True):
    if not use_kernel:
        return ref_paged_prefill_attention(q, k_pages, v_pages, block_table,
                                           q_start, new_lens, softcap=softcap)
    return _paged_prefill(q, k_pages, v_pages, block_table, q_start, new_lens,
                          softcap=softcap, interpret=interpret_mode())
