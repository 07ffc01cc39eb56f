"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret mode on CPU), plus hypothesis property tests on invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops
from repro.kernels.ref import ref_paged_attention, ref_prefill_attention

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,KV,hd", [
    (1, 8, 8, 2, 2, 32),      # MHA, no history
    (2, 16, 48, 4, 2, 64),    # GQA, chunked (history = 32)
    (1, 24, 40, 8, 1, 128),   # MQA, odd chunk size
    (2, 5, 21, 4, 4, 64),     # non-divisible by block sizes -> padding
])
def test_prefill_kernel_sweep(B, Sq, Skv, H, KV, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, hd)).astype(dtype)
    q_start = Skv - Sq
    out = ops.prefill_attention(q, k, v, q_start=q_start)
    ref = ref_prefill_attention(q, k, v, q_start=q_start)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype))


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (8, 0.0), (0, 30.0),
                                            (16, 50.0)])
def test_prefill_kernel_window_softcap(window, softcap):
    B, Sq, Skv, H, KV, hd = 2, 16, 48, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Skv, KV, hd))
    v = jax.random.normal(ks[2], (B, Skv, KV, hd))
    out = ops.prefill_attention(q, k, v, q_start=32, window=window,
                                softcap=softcap)
    ref = ref_prefill_attention(q, k, v, q_start=32, window=window,
                                softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,hd,P,page,mp", [
    (1, 2, 2, 32, 8, 8, 2),
    (2, 4, 2, 64, 16, 8, 4),
    (3, 8, 1, 128, 32, 16, 3),
    (2, 8, 8, 64, 16, 4, 5),
])
def test_paged_kernel_sweep(B, H, KV, hd, P, page, mp, dtype):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, page, KV, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, page, KV, hd)).astype(dtype)
    bt = jax.random.randint(ks[3], (B, mp), 0, P)
    lengths = jax.random.randint(ks[4], (B,), 1, mp * page + 1)
    out = ops.paged_attention(q, kp, vp, bt, lengths)
    ref = ref_paged_attention(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype))


def test_paged_kernel_ignores_unmapped_pages():
    """Entries of the page table beyond `length` must not affect output."""
    B, H, KV, hd, P, page, mp = 1, 4, 2, 32, 8, 4, 4
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, page, KV, hd))
    vp = jax.random.normal(ks[2], (P, page, KV, hd))
    lengths = jnp.array([6], jnp.int32)  # only pages 0-1 used
    bt1 = jnp.array([[0, 1, 2, 3]], jnp.int32)
    bt2 = jnp.array([[0, 1, 7, 5]], jnp.int32)  # junk tail
    o1 = ops.paged_attention(q, kp, vp, bt1, lengths)
    o2 = ops.paged_attention(q, kp, vp, bt2, lengths)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(sq=st.integers(1, 12), hist=st.integers(0, 12),
       h=st.sampled_from([2, 4]), kv=st.sampled_from([1, 2]))
def test_prefill_kernel_property(sq, hist, h, kv):
    """Property: kernel == oracle for arbitrary chunk/history splits."""
    hd = 32
    skv = hist + sq
    ks = jax.random.split(jax.random.PRNGKey(sq * 100 + hist), 3)
    q = jax.random.normal(ks[0], (1, sq, h, hd))
    k = jax.random.normal(ks[1], (1, skv, kv, hd))
    v = jax.random.normal(ks[2], (1, skv, kv, hd))
    out = ops.prefill_attention(q, k, v, q_start=hist)
    ref = ref_prefill_attention(q, k, v, q_start=hist)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------- paged chunked-prefill kernel ------------------------------

def _paged_prefill_case(hists, chunks, *, page=4, KV=2, H=4, hd=32, seed=0,
                        junk_tail=15, maxp=None):
    """Write per-row history+chunk into disjoint pages; return kernel args.
    Block-table tails beyond each row's live pages hold ``junk_tail``."""
    from repro.cache.paged import PagedKVStore
    B = len(hists)
    totals = [h + c for h, c in zip(hists, chunks)]
    n_pages = [max(1, -(-t // page)) for t in totals]
    if maxp is None:
        maxp = max(n_pages)
    P = sum(n_pages) + 1
    store = PagedKVStore.create(P, page, KV, hd, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 * B + 1)
    S = max(max(chunks), 1)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    bt_rows, nxt = [], 0
    for b in range(B):
        pages = list(range(nxt, nxt + n_pages[b]))
        nxt += n_pages[b]
        if totals[b]:
            k = jax.random.normal(ks[1 + 2 * b], (totals[b], KV, hd))
            v = jax.random.normal(ks[2 + 2 * b], (totals[b], KV, hd))
            store = store.write(k, v, pages, start=0)
        bt_rows.append((pages + [junk_tail] * maxp)[:maxp])
    bt = jnp.asarray(bt_rows, jnp.int32)
    return (q, store.k_pages, store.v_pages, bt,
            jnp.asarray(hists, jnp.int32), jnp.asarray(chunks, jnp.int32))


@pytest.mark.parametrize("hists,chunks", [
    ([0], [1]),               # no history, single token
    ([0, 5, 9], [6, 4, 0]),   # ragged incl. a length-0 row
    ([3], [6]),               # chunk crosses a page boundary mid-write
    ([4, 8], [4, 8]),         # history and chunk both page-aligned
    ([2, 2, 2, 2, 2], [3, 3, 3, 3, 3]),  # batch crossing a pow2 boundary
])
def test_paged_prefill_kernel_matches_ref(hists, chunks):
    from repro.kernels.ref import ref_paged_prefill_attention
    args = _paged_prefill_case(hists, chunks)
    out = ops.paged_prefill_attention(*args)
    ref = ref_paged_prefill_attention(*args)
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_prefill_kernel_gqa_softcap(H, KV, softcap):
    from repro.kernels.ref import ref_paged_prefill_attention
    args = _paged_prefill_case([5, 0, 9], [6, 4, 2], H=H, KV=KV, seed=2)
    out = ops.paged_prefill_attention(*args, softcap=softcap)
    ref = ref_paged_prefill_attention(*args, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_paged_prefill_kernel_query_tiles():
    """Chunks longer than one query tile (G=8 -> 128 queries per tile):
    a row spanning two tiles, one whose second tile is part padding, and
    one whose second tile is all padding (it reads no pages)."""
    from repro.kernels.ref import ref_paged_prefill_attention
    args = _paged_prefill_case([3, 0, 5], [200, 130, 20], page=8, H=8, KV=1,
                               seed=3)
    out = ops.paged_prefill_attention(*args)
    ref = ref_paged_prefill_attention(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_paged_prefill_length_zero_rows_are_exact_zero():
    args = _paged_prefill_case([0, 7], [0, 3])
    out = np.asarray(ops.paged_prefill_attention(*args))
    assert (out[0] == 0).all()
    # padding query positions of the live row are zeroed too
    assert (out[1, 3:] == 0).all() and np.abs(out[1, :3]).sum() > 0


@settings(max_examples=12, deadline=None)
@given(hist=st.integers(0, 13), chunk=st.integers(1, 9),
       h=st.sampled_from([2, 4]), kv=st.sampled_from([1, 2]),
       pad_pages=st.integers(0, 3))
def test_paged_prefill_kernel_property(hist, chunk, h, kv, pad_pages):
    """Property: kernel == oracle for arbitrary history/chunk splits and
    padded (bucketed) table widths; the chunk attends over pages only."""
    from repro.kernels.ref import ref_paged_prefill_attention
    live = max(1, -(-(hist + chunk) // 4))
    args = _paged_prefill_case([hist], [chunk], H=h, KV=kv,
                               seed=hist * 100 + chunk,
                               maxp=live + pad_pages)
    out = ops.paged_prefill_attention(*args)
    ref = ref_paged_prefill_attention(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_paged_prefill_kernel_matches_contiguous_oracle():
    """Paged kernel == the dense chunked-prefill oracle on the same
    history/chunk (ties the paged path to the non-paged ground truth)."""
    from repro.cache.paged import PagedKVStore
    from repro.kernels.ref import ref_prefill_attention
    page, KV, H, hd = 4, 2, 4, 32
    hist, chunk = 9, 6
    ks = jax.random.split(KEY, 3)
    k = jax.random.normal(ks[0], (hist + chunk, KV, hd))
    v = jax.random.normal(ks[1], (hist + chunk, KV, hd))
    q = jax.random.normal(ks[2], (1, chunk, H, hd))
    pages = [7, 2, 9, 4]
    store = PagedKVStore.create(12, page, KV, hd, dtype=jnp.float32)
    store = store.write(k, v, pages, start=0)
    out = ops.paged_prefill_attention(
        q, store.k_pages, store.v_pages, jnp.asarray([pages], jnp.int32),
        jnp.asarray([hist], jnp.int32), jnp.asarray([chunk], jnp.int32))
    ref = ref_prefill_attention(q, k[None], v[None], q_start=hist)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_paged_kernel_clamped_padding_dma_matches_ref():
    """The decode kernel's index_map clamps padded grid steps to the
    row's last live page (no trash-page DMA per masked step); outputs
    must be unchanged — including rows whose table is almost all padding
    and a length-0 row whose clamp floor is page 0."""
    from repro.kernels.ref import ref_paged_attention
    B, H, KV, hd, P, page, mp = 3, 4, 2, 32, 16, 4, 8
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, page, KV, hd))
    vp = jax.random.normal(ks[2], (P, page, KV, hd))
    lengths = jnp.asarray([5, 0, 32], jnp.int32)   # 2 live pages / 0 / all
    bt = jax.random.randint(ks[3], (B, mp), 0, P)
    out = ops.paged_attention(q, kp, vp, bt, lengths)
    ref = ref_paged_attention(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # junk in the padded tail (incl. out-of-range-looking last page id)
    # cannot leak into the output through the clamped restaging
    bt2 = bt.at[:, 2:].set(P - 1)
    out2 = ops.paged_attention(q, kp, vp, bt2.at[0, 2:].set(11), lengths)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out2[0]),
                               atol=1e-6)


def test_prefill_chunks_equal_full():
    """Running prefill in two chunks == one full pass (engine invariant)."""
    B, S, H, KV, hd = 1, 32, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    full = ops.prefill_attention(q, k, v, q_start=0)
    c1 = ops.prefill_attention(q[:, :16], k[:, :16], v[:, :16], q_start=0)
    c2 = ops.prefill_attention(q[:, 16:], k, v, q_start=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([c1, c2], 1)),
                               np.asarray(full), atol=2e-5)
