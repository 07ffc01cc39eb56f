"""Paged chunked-prefill flash attention Pallas kernel (TPU target).

The serving engine's prefill hot-spot on the batched paged path: each
row's chunk of Sq queries (global positions q_start[b]..q_start[b]+
new_lens[b]) attends causally over that row's block-table-indexed pages —
the chunk's own K/V have already been scattered into the pages, so the
kernel reads context exclusively through the table. This replaces the
gather-pages-then-dense-mha materialization: attention traffic scales
with the table width the caller passes (length-bucketed by the executor)
instead of the context cap.

Grid: (B, query tiles, max_pages) — page axis innermost, same
scalar-prefetch pattern as the decode kernel (``paged_attention``): the
block table, per-row ``q_start`` and ``new_lens`` ride in SMEM so the
BlockSpec index_map can stage exactly the needed K/V page HBM→VMEM per
step. Online softmax across key pages with a (KV, G*tq, hd) accumulator
per query tile in VMEM scratch; per-row causal masking against the
ragged ``q_start``/``new_lens`` vectors. Steps past a tile's last live
page (its causal bound) are predicated off AND their index_map is
clamped to that page, so masked steps restage a resident page instead of
DMAing a fresh one.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(block_table, q_start, new_lens, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size: int, max_pages: int,
            tq: int, softcap: float, sm_scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    total = q_start[b] + new_lens[b]
    n_pages = _live_pages(q_start[b], new_lens[b], t, page_size, tq)

    @pl.when(p < n_pages)
    def _step():
        rows = q_ref.shape[1]                        # G * tq
        # row r holds query r % tq of this tile (rows are g-major)
        qpos = q_start[b] + t * tq + (jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) & (tq - 1))
        tpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        # causal over history + chunk; the total clamp only matters for
        # padding queries (i >= new_lens), whose outputs are discarded —
        # it keeps them off stale page tails all the same
        live = (tpos <= qpos) & (tpos < total)
        for h in range(q_ref.shape[0]):
            q = q_ref[h].astype(jnp.float32)            # (G*tq, hd)
            k = k_ref[:, h, :].astype(jnp.float32)      # (page_size, hd)
            v = v_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, _NT) * sm_scale  # (G*tq, T)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(live, s, NEG_INF)

            m_prev = m_ref[h]                            # (G*tq, 1)
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

# query rows (G * tq) per grid step: bounds the f32 accumulator and the
# q/out blocks to a few MiB of VMEM at any chunk length
_Q_ROWS = 1024


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def _live_pages(q_start, new_len, t, page_size: int, tq: int):
    """Key pages query tile ``t`` reads: up to its last query's causal
    bound (0 for a tile of padding queries)."""
    hi = q_start + jnp.minimum(new_len, (t + 1) * tq)
    return jnp.where(t * tq < new_len, (hi + page_size - 1) // page_size, 0)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, block_table, q_start,
                            new_lens, *, softcap: float = 0.0,
                            interpret: bool = True):
    """q: (B,Sq,H,hd) right-padded chunks; k_pages/v_pages:
    (P,page_size,KV,hd) — already containing the chunk's K/V;
    block_table: (B,max_pages) int32; q_start: (B,) int32 context tokens
    before each chunk; new_lens: (B,) int32 valid chunk tokens (<= Sq).
    -> (B,Sq,H,hd); outputs at padding positions (i >= new_lens[b]) are
    exact zeros, matching ``ref_paged_prefill_attention``.

    The page pool is read in place (whole-page blocks whose last two dims
    span the array's: no head or lane padding, no pool copy). Queries are
    tiled into the grid: ``tq`` queries of every head in a KV group form
    one ``(G*tq, hd)`` block, so VMEM stays bounded however long the chunk.
    """
    B, Sq, H, hd = q.shape
    P, page_size, KV, _ = k_pages.shape
    max_pages = block_table.shape[1]
    G = H // KV
    # queries per tile: a power of two (the kernel takes r % tq as a mask)
    tq = min(_pow2_floor(max(_Q_ROWS // G, 8)),
             1 << (max(Sq, 8) - 1).bit_length())
    nT = -(-Sq // tq)
    qp = jnp.pad(q, ((0, 0), (0, nT * tq - Sq), (0, 0), (0, 0)))
    # (B, nT*tq, H, hd) -> (B, nT, KV, G*tq, hd): g-major rows per tile
    qt = qp.reshape(B, nT, tq, KV, G, hd).transpose(0, 1, 3, 4, 2, 5) \
        .reshape(B, nT, KV, G * tq, hd)

    kernel = functools.partial(
        _kernel, page_size=page_size, max_pages=max_pages, tq=tq,
        softcap=softcap, sm_scale=1.0 / math.sqrt(hd))

    def _kv_map(b, t, p, bt, qs, nl):
        # clamp grid steps past the tile's live pages to its last one:
        # the @pl.when(p < n_pages) predicate discards the compute, and the
        # clamped index means the DMA restages an already-resident page
        # instead of streaming a fresh one per masked step
        last = jnp.maximum(
            _live_pages(qs[b], nl[b], t, page_size, tq) - 1, 0)
        return (bt[b, jnp.minimum(p, last)], 0, 0, 0)

    sq = pl.squeezed
    q_spec = pl.BlockSpec((sq, sq, KV, G * tq, hd),
                          lambda b, t, p, bt, qs, nl: (b, t, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nT, max_pages),
        in_specs=[
            q_spec,
            pl.BlockSpec((sq, page_size, KV, hd), _kv_map),
            pl.BlockSpec((sq, page_size, KV, hd), _kv_map),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, G * tq, 1), jnp.float32),
            pltpu.VMEM((KV, G * tq, 1), jnp.float32),
            pltpu.VMEM((KV, G * tq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(block_table, q_start, new_lens, qt, k_pages, v_pages)
    out = out.reshape(B, nT, KV, G, tq, hd).transpose(0, 1, 4, 2, 3, 5) \
        .reshape(B, nT * tq, H, hd)[:, :Sq]
    # padding queries (and whole padding rows): exact zeros, like the ref
    pad = jnp.arange(Sq, dtype=jnp.int32)[None, :] < new_lens[:, None]
    return jnp.where(pad[:, :, None, None], out, 0.0)
