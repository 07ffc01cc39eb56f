"""Paged decode attention Pallas kernel (TPU target).

The serving engine's decode hot-spot: one query token per sequence attends
over a block-table-indexed paged KV cache. TPU adaptation of vLLM's
PagedAttention (see DESIGN.md): pages are dense (num_pages, page_size, KV,
hd) arrays; the block table rides in scalar-prefetch SMEM so the BlockSpec
index_map can stage exactly the needed K/V page HBM->VMEM per grid step.

Grid: (B, max_pages) — page axis innermost; online softmax across pages with
the (KV, G, hd) accumulator in VMEM scratch (G = query heads per KV head).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(block_table, lengths, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size: int, max_pages: int,
            softcap: float, sm_scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths[b]
    n_pages = (length + page_size - 1) // page_size

    @pl.when(p < n_pages)
    def _step():
        tpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        # one 2-D matmul pair per KV head: the page block holds every head,
        # and a static head index is a plain strided VMEM load
        for h in range(q_ref.shape[0]):
            q = q_ref[h].astype(jnp.float32)            # (G, hd)
            k = k_ref[:, h, :].astype(jnp.float32)      # (page_size, hd)
            v = v_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, _NT) * sm_scale      # (G, T)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(tpos < length, s, NEG_INF)

            m_prev = m_ref[h]                            # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(pexp, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(pexp, v)
            m_ref[h] = m_new

    @pl.when(p == max_pages - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


# contract the last dims of both operands: (M, d) x (N, d) -> (M, N)
_NT = (((1,), (1,)), ((), ()))


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    softcap: float = 0.0, interpret: bool = True):
    """q: (B,H,hd); k_pages/v_pages: (P,page_size,KV,hd);
    block_table: (B,max_pages) int32; lengths: (B,) int32. -> (B,H,hd).

    The page pool is read in place: each K/V block is one whole page,
    ``(page_size, KV, hd)``, whose last two dims span the array's, so no
    head or lane padding is needed and nothing copies the pool.
    """
    B, H, hd = q.shape
    P, page_size, KV, _ = k_pages.shape
    max_pages = block_table.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)

    kernel = functools.partial(
        _kernel, page_size=page_size, max_pages=max_pages, softcap=softcap,
        sm_scale=1.0 / math.sqrt(hd))

    def _kv_map(b, p, bt, ln):
        # grid steps past the row's live pages are predicated off by
        # @pl.when(p < n_pages), but the BlockSpec pipeline would still
        # stage bt[b, p] (a trash/padding page) HBM→VMEM every masked
        # step; clamping to the row's last valid page makes those steps
        # restage an already-resident page — a no-op DMA — instead
        last = jnp.maximum((ln[b] + page_size - 1) // page_size - 1, 0)
        return (bt[b, jnp.minimum(p, last)], 0, 0, 0)

    sq = pl.squeezed
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((sq, KV, G, hd), lambda b, p, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((sq, page_size, KV, hd), _kv_map),
            pl.BlockSpec((sq, page_size, KV, hd), _kv_map),
        ],
        out_specs=pl.BlockSpec((sq, KV, G, hd),
                               lambda b, p, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(block_table, lengths, qg, k_pages, v_pages)
    # length-0 guard (padding rows in bucketed batches): the accumulator
    # never ran, so force exact zeros rather than 0/eps division noise.
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(B, H, hd)
