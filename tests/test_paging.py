"""Paged-KV building blocks: PagePool alloc/free/refcount invariants, the
radix prefix cache (match/insert/evict, COW on divergence, hit accounting),
the model's writes into the pools, and paged flash-decode parity — the
oracle's page-gather against attention over the dense rows, and the Pallas
paged kernel (interpret) against the oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _oracle import attend_rows

from repro.kernels import ref
from repro.kernels import decode_attention
from repro.kernels.decode_attention import flash_decode
from repro.serving import PagePool, RadixCache

RNG = np.random.RandomState(11)


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------

def test_pool_alloc_until_exhausted():
    pool = PagePool(5)  # pages 1..4 usable, 0 is the trash page
    assert pool.num_free == 4 and pool.num_used == 0
    got = [pool.alloc() for _ in range(4)]
    assert sorted(got) == [1, 2, 3, 4]  # trash page never handed out
    assert all(pool.refcount(p) == 1 for p in got)
    assert pool.alloc() is None  # exhausted -> None, not an exception
    assert pool.num_free == 0 and pool.num_used == 4


def test_pool_free_via_decref_and_reuse():
    pool = PagePool(3)
    a = pool.alloc()
    b = pool.alloc()
    pool.decref(a)
    assert pool.num_free == 1
    c = pool.alloc()
    assert c == a  # freed page is reusable
    assert pool.refcount(b) == 1 and pool.refcount(c) == 1


def test_pool_refcount_sharing():
    pool = PagePool(4)
    p = pool.alloc()
    pool.incref(p)  # second holder (e.g. the radix tree)
    pool.incref(p)  # third
    assert pool.refcount(p) == 3
    pool.decref(p)
    pool.decref(p)
    assert pool.num_free == 2  # still held once: not freed
    pool.decref(p)
    assert pool.num_free == 3  # last ref -> back on the free list


def test_pool_trash_page_pinned():
    pool = PagePool(2)
    assert pool.refcount(0) == 1  # pinned forever
    with pytest.raises(AssertionError):
        pool.decref(0)
    with pytest.raises(AssertionError):
        pool.incref(0)
    with pytest.raises(ValueError):
        PagePool(1)  # no usable pages


def test_pool_double_free_is_detected():
    pool = PagePool(3)
    p = pool.alloc()
    pool.decref(p)
    with pytest.raises(AssertionError):
        pool.decref(p)


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------

def _cache(ps=4, n_pages=32):
    pool = PagePool(n_pages)
    return RadixCache(ps, pool), pool


def _insert_prompt(rc, pool, tokens):
    """Simulate an admission: alloc a page per full chunk, insert."""
    ps = rc.page_size
    pages = [pool.alloc() for _ in range(len(tokens) // ps)]
    rc.insert(tokens, pages)
    return pages


def test_radix_miss_then_full_hit():
    rc, pool = _cache()
    prompt = list(range(12))  # 3 full pages of 4
    m = rc.match(prompt)
    assert m.tokens == 0 and not m.full_pages and m.partial is None
    pages = _insert_prompt(rc, pool, prompt)
    assert all(pool.refcount(p) == 2 for p in pages)  # seq + tree
    m = rc.match(prompt)
    assert m.full_pages == pages and m.tokens == 12 and m.partial is None
    # accounting: 0/12 then 12/12 matched
    assert rc.lookup_tokens == 24 and rc.hit_tokens == 12
    assert rc.hit_rate == 0.5


def test_radix_max_match_caps_the_hit():
    """Engines cap at plen - 1 so at least one token remains to prefill."""
    rc, pool = _cache()
    prompt = list(range(8))
    _insert_prompt(rc, pool, prompt)
    m = rc.match(prompt, max_match=7)
    assert len(m.full_pages) == 1  # second page would need all 8 tokens
    assert m.partial is not None and m.partial[1] == 3  # 3-row COW share
    assert m.tokens == 7


def test_radix_partial_page_cow_on_divergence():
    """A prompt diverging inside a cached page shares it copy-on-write:
    match returns the donor page + the number of identical leading rows."""
    rc, pool = _cache()
    donor_pages = _insert_prompt(rc, pool, [1, 2, 3, 4, 5, 6, 7, 8])
    m = rc.match([1, 2, 3, 4, 5, 6, 99, 100])  # diverges at row 2 of page 2
    assert m.full_pages == donor_pages[:1]
    assert m.partial == (donor_pages[1], 2)
    assert m.tokens == 6
    # divergence at row 0 of the first page: nothing shareable
    m = rc.match([9, 9, 9, 9])
    assert m.tokens == 0 and m.partial is None


def test_radix_insert_existing_chunks_no_double_incref():
    rc, pool = _cache()
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    pages = _insert_prompt(rc, pool, prompt)
    again = [pool.alloc(), pool.alloc()]
    assert rc.insert(prompt, again) == 0  # all chunks already cached
    assert all(pool.refcount(p) == 2 for p in pages)
    assert all(pool.refcount(p) == 1 for p in again)  # untouched


def test_radix_evict_lru_leaves_first():
    rc, pool = _cache(ps=4, n_pages=6)  # 5 usable pages
    a = _insert_prompt(rc, pool, [1, 2, 3, 4, 5, 6, 7, 8])  # chain a1 -> a2
    b = _insert_prompt(rc, pool, [9, 9, 9, 9])
    for p in a + b:
        pool.decref(p)  # sequences retire; only the tree holds the pages
    rc.match([1, 2, 3, 4, 5, 6, 7, 8])  # touch chain a: b becomes LRU
    assert pool.num_free == 2
    assert rc.evict(3) == 1  # b's leaf goes first
    assert pool.refcount(b[0]) == 0 and pool.refcount(a[1]) == 1
    # inner node a1 only becomes evictable after its leaf a2 goes
    assert rc.evict(5) == 2
    assert pool.num_free == 5


def test_radix_evict_skips_referenced_pages():
    rc, pool = _cache(ps=4, n_pages=4)
    pages = _insert_prompt(rc, pool, [1, 2, 3, 4])  # rc == 2: seq still live
    assert rc.evict(10) == 0  # nothing evictable
    assert pool.refcount(pages[0]) == 2
    pool.decref(pages[0])
    assert rc.evict(10) == 1  # now only the tree held it
    assert pool.num_free == 3


def test_radix_num_evictable_tracks_refs_and_structure():
    """num_evictable counts exactly what leaf-inward eviction can reach:
    tree-only pages whose whole subtree is also tree-only."""
    rc, pool = _cache(ps=4, n_pages=8)
    a = _insert_prompt(rc, pool, [1, 2, 3, 4, 5, 6, 7, 8])  # chain a1 -> a2
    b = _insert_prompt(rc, pool, [9, 9, 9, 9])
    assert rc.num_evictable() == 0  # every page still sequence-held
    pool.decref(b[0])
    assert rc.num_evictable() == 1
    pool.decref(a[1])  # leaf a2 tree-only, but inner a1 still held
    assert rc.num_evictable() == 2
    pool.decref(a[0])
    assert rc.num_evictable() == 3
    pool.incref(a[1])  # re-pin the leaf: a1 is unreachable again
    assert rc.num_evictable() == 1
    pool.decref(a[1])
    n = rc.num_evictable()
    assert rc.evict(10) == n == 3  # the count is exactly what evict frees
    assert rc.num_evictable() == 0


def test_radix_clear_releases_tree_refs():
    rc, pool = _cache()
    pages = _insert_prompt(rc, pool, list(range(8)))
    for p in pages:
        pool.decref(p)
    rc.clear()
    assert pool.num_free == 31
    assert rc.match(list(range(8))).tokens == 0


# ---------------------------------------------------------------------------
# paged flash decode: oracle gather == dense linear; kernel == oracle
# ---------------------------------------------------------------------------

def _paged_fixture(B=3, H=4, K=2, P=9, ps=8, d=16, dv=None, seed=3):
    """Head-major page pools [P, K, ps, d] + tables + the equivalent dense
    [B, S, K, d] caches."""
    rng = np.random.RandomState(seed)
    dv = dv or d
    npp = (P - 1) // B  # pages per sequence (page 0 reserved)
    kp = rng.randn(P, K, ps, d).astype(np.float32) * 0.3
    vp = rng.randn(P, K, ps, dv).astype(np.float32) * 0.3
    # non-trivial tables: sequence b owns a scattered set of pages
    perm = rng.permutation(np.arange(1, P))[: B * npp].reshape(B, npp)
    kd = kp[perm].transpose(0, 1, 3, 2, 4).reshape(B, npp * ps, K, d)
    vd = vp[perm].transpose(0, 1, 3, 2, 4).reshape(B, npp * ps, K, dv)
    q = jnp.asarray(rng.randn(B, H, d) * 0.3, jnp.float32)
    pos = jnp.asarray(rng.randint(0, npp * ps, B), jnp.int32)
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(perm, jnp.int32),
            jnp.asarray(kd), jnp.asarray(vd), pos)


def test_paged_ref_equals_dense_linear():
    """The page table is pure indirection: the oracle over (pools, table)
    must equal attention over the densely gathered rows."""
    q, kp, vp, tbl, kd, vd, pos = _paged_fixture()
    want = attend_rows(q, kd, vd, pos)
    got = ref.flash_decode_ref(q, kp, vp, pos, None, pages=tbl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def _slot_mix(K, G, ps, dq, dv, seed):
    """Five slots over head-major pools [P, K, ps, d] of ``npp = 3`` pages
    each: a drained slot (trash page, ``start > pos``), a windowed slot
    (``start > 0``, the window across a page boundary), a slot at capacity
    (``pos == S - 1``), a frozen slot on the trash page (``pos == S``) and
    a plain one.  ``dv < dq`` is MLA's form: one fused pool as k and v."""
    B, npp = 5, 3
    S, P = npp * ps, 1 + 3 * npp
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, K * G, dq) * 0.3, jnp.float32)
    kp = jnp.asarray(rng.randn(P, K, ps, dq) * 0.3, jnp.float32)
    vp = kp if dv < dq else jnp.asarray(rng.randn(P, K, ps, dq) * 0.3,
                                        jnp.float32)
    live = rng.permutation(np.arange(1, P)).reshape(3, npp)
    tbl = jnp.asarray(np.stack([np.zeros(npp), live[0], live[1],
                                np.zeros(npp), live[2]]), jnp.int32)
    w = ps + 5
    pw = S - ps - 3
    pos = jnp.asarray([0, pw, S - 1, S, int(rng.randint(0, S))], jnp.int32)
    start = jnp.asarray([1, pw - w + 1, 0, 0, 0], jnp.int32)
    return q, kp, vp, tbl, pos, start


# (K, G, page rows, qk dim, v dim): MLA's latent pool (K = 1, v narrowed),
# GQA, MHA; each at pages of 8, 16 and 64 rows
_HEADS = [(1, 4, 48, 32), (4, 4, 16, 16), (8, 8, 16, 16), (16, 1, 16, 16)]
_CASES = [pytest.param(0.0, False, None, id="0.0-False"),
          pytest.param(25.0, True, None, id="25.0-True")] + [
    pytest.param(25.0 if K == 16 else 0.0, True, (K, G, ps, dq, dv),
                 id=f"K{K}-G{G}-ps{ps}" + ("-mla" if dv < dq else ""))
    for K, G, dq, dv in _HEADS for ps in (8, 16, 64)]


@pytest.mark.parametrize("softcap,with_start,case", _CASES)
def test_paged_kernel_matches_oracle(softcap, with_start, case):
    if case is None:
        q, kp, vp, tbl, _, _, pos = _paged_fixture()
        start = (jnp.minimum(pos, jnp.asarray([3, 0, 11], jnp.int32))
                 if with_start else None)
        dv = None
    else:
        K, G, ps, dq, dv = case
        q, kp, vp, tbl, pos, start = _slot_mix(K, G, ps, dq, dv, seed=K + ps)
    want = ref.flash_decode_ref(q, kp, vp, pos, start, pages=tbl,
                                softcap=softcap, dv=dv)
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, softcap=softcap,
                       dv=dv, interpret=True)
    if case is not None:
        assert np.all(np.asarray(got[0]) == 0.0)  # drained: exact zeros
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("budget_heads", [1, 2, 4])
def test_paged_kernel_head_blocks(monkeypatch, budget_heads):
    """A VMEM budget that holds fewer than all K heads splits them into
    blocks of the largest divisor that fits; the result is unchanged."""
    K, G, ps, d = 8, 2, 16, 16
    monkeypatch.setattr(decode_attention, "KV_VMEM_BUDGET",
                        budget_heads * 2 * ps * 2 * d * 4)
    spec = decode_attention.fd_paged_spec(5, K * G, K, d, d, ps, 3, 10,
                                          itemsize=4)
    assert spec.grid == (5, K // budget_heads, 3)
    assert spec.operands[1].block_shape == (None, 1, budget_heads, ps, d)
    q, kp, vp, tbl, pos, start = _slot_mix(K, G, ps, d, d, seed=21)
    want = ref.flash_decode_ref(q, kp, vp, pos, start, pages=tbl)
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


def test_paged_heads_largest_divisor_in_budget():
    fit = decode_attention.fd_paged_heads
    assert fit(16, 128, 128, 64, 2) == 16  # olmo-1b at 64-row bf16 pages
    # 12 heads, a budget of 5 heads' blocks: 4 is the largest divisor
    big = decode_attention.KV_VMEM_BUDGET // 5 // (2 * 2 * 2)
    assert fit(12, big, big, 1, 2) == 4
    assert fit(7, big, big, 1, 2) == 1  # no divisor but 1 fits


def _stack_layers(pool, layer, n=3, seed=0):
    """``pool`` as layer ``layer`` of ``n`` stacked pools whose other layers
    hold other values: a read that ignores the layer index reads those."""
    rng = np.random.RandomState(seed)
    layers = [jnp.asarray(rng.randn(*pool.shape), pool.dtype)
              for _ in range(n)]
    layers[layer] = pool
    return jnp.stack(layers)


@pytest.mark.parametrize("layer", [None, 0, 2])
def test_paged_kernel_window_via_start(layer):
    """Sliding windows under paging express validity as start = pos - w + 1
    over logical rows — must equal attention over the dense rows restricted
    to the window.  ``layer``: the pools are read as layer 0 or
    2 of a stack of three, through the kernel's layer index (``None``: one
    layer's bare pools)."""
    q, kp, vp, tbl, kd, vd, pos = _paged_fixture(seed=5)
    if layer is not None:
        kp, vp = (_stack_layers(kp, layer, seed=1),
                  _stack_layers(vp, layer, seed=2))
    w = 10
    start = jnp.maximum(pos - w + 1, 0)
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, layer=layer,
                       interpret=True)
    oracle = ref.flash_decode_ref(q, kp, vp, pos, start, pages=tbl,
                                  layer=layer)
    want = attend_rows(q, kd, vd, pos, start)
    for out in (got, oracle):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("ps", [8, 32])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_page_row_writes_land_in_place(kind, ps):
    """The model's KV row writes into layer 1 of three stacked pools: each
    row lands at ``(layer, pages[b, r // ps], :, r % ps)``; other layers,
    rows and pages keep their values; rows off the end of the table and a
    chunk's padding rows are dropped, never clamped.  Pages of 32 rows are
    written a 16-row tile at a time, pages of 8 whole."""
    from repro.models.layers import _page_row_write, _page_rows_write

    L, P, K, d, npp, layer = 3, 9, 2, 4, 3, 1
    rng = np.random.RandomState(ps)
    pools = [rng.randn(L, P, K, ps, d).astype(np.float32) for _ in range(2)]
    S = npp * ps
    if kind == "decode":
        # a row mid-page, one at the table's last row, one past its end
        # (dropped) and a retired slot whose table is the trash page
        tbl = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 8], [0, 0, 0]])
        pos = np.array([5, S - 1, S, 3])
        rows = [rng.randn(4, K, d).astype(np.float32) for _ in range(2)]
        got = _page_row_write(tuple(map(jnp.asarray, pools)),
                              tuple(map(jnp.asarray, rows)),
                              jnp.asarray(tbl, jnp.int32),
                              jnp.asarray(pos, jnp.int32), jnp.int32(layer))
        writes = [(b, pos[b], b, 0) for b in range(4)]
    else:
        # a chunk from mid-page with padding after 9 rows, and one that
        # runs off the end of its table
        C = ps + 4
        tbl = np.array([[1, 2, 3], [4, 5, 6]])
        pos0, n = np.array([5, S - 6]), np.array([9, C])
        rows = [rng.randn(2, C, K, d).astype(np.float32) for _ in range(2)]
        got = _page_rows_write(tuple(map(jnp.asarray, pools)),
                               tuple(map(jnp.asarray, rows)),
                               jnp.asarray(tbl, jnp.int32),
                               jnp.asarray(pos0, jnp.int32),
                               jnp.asarray(n, jnp.int32), jnp.int32(layer))
        writes = [(b, pos0[b] + i, b, i) for b in range(2)
                  for i in range(n[b])]
    for pool, row, out in zip(pools, rows, got):
        want = pool.copy()
        for b, r, src, i in writes:
            if r // ps < npp:
                want[layer, tbl[b, r // ps], :, r % ps] = (
                    row[src] if kind == "decode" else row[src, i])
        np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("arch", ["olmo-1b", "minicpm3-4b",
                                  "jamba-v0.1-52b"])
def test_write_prefill_reads_back_row_for_row(arch):
    """``M.write_prefill`` puts row ``r`` of a whole prefill, every layer,
    at ``(table[r // ps], r % ps)`` of its pool — head-major attention
    pools and MLA's latent pool alike — through a scattered table, and
    overwrites batch row ``slot`` of a stateful leaf (SSM state); nothing
    else in the tree changes."""
    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    from repro.models.params import is_spec

    cfg = reduce_config(get_config(arch))
    params = M.init(cfg, jax.random.PRNGKey(0))
    n, ps, P, slot = 21, 8, 9, 1  # two full pages and part of a third
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, n), 0,
                                cfg.vocab_size)
    _, small = M.prefill(cfg, params, {"tokens": tokens})
    table = np.array([7, 2, 5, 0])  # the last entry is never reached
    got = M.write_prefill(cfg, M.init_paged_cache(cfg, 2, P, ps), small,
                          jnp.asarray(table, jnp.int32), slot)
    specs = jax.tree.leaves(M.paged_cache_specs(cfg, 2, P, ps),
                            is_leaf=is_spec)
    for spec, out, sm in zip(specs, jax.tree.leaves(got),
                             jax.tree.leaves(small), strict=True):
        out, sm = np.asarray(out), np.asarray(sm)
        want = np.zeros_like(out)
        if "kv_seq" not in spec.axes:
            want[:, slot] = sm[:, 0]
        for r in range(n if "kv_seq" in spec.axes else 0):
            page, off = table[r // ps], r % ps
            if "kv_heads" in spec.axes:  # [R, P, K, ps, d]
                want[:, page, :, off] = sm[:, 0, r]
            else:  # MLA: [R, P, ps, d]
                want[:, page, off] = sm[:, 0, r]
        np.testing.assert_array_equal(out, want, err_msg=str(spec.axes))


def test_paged_kernel_mla_fused_operand():
    """MLA's dual-operand form through the paged path: one fused
    [latent | rope] pool passed as both k and v with dv narrowing."""
    B, H, P, ps, kvr, dr = 2, 8, 7, 8, 32, 16
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(B, H, kvr + dr) * 0.3, jnp.float32)
    kv = jnp.asarray(rng.randn(P, 1, ps, kvr + dr) * 0.3, jnp.float32)
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([7, 20], jnp.int32)
    got = flash_decode(q, kv, kv, pos, None, pages=tbl, scale=0.13, dv=kvr,
                       interpret=True)
    want = ref.flash_decode_ref(q, kv, kv, pos, None, pages=tbl, scale=0.13,
                                dv=kvr)
    assert got.shape == (B, H, kvr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


def test_paged_kernel_empty_and_fresh_slots():
    """Retired slots (table all trash-page zeros, pos=0, start>pos) return
    exact zeros; a fresh slot attends exactly its single live row."""
    B, H, K, P, ps, d = 2, 4, 2, 5, 8, 16
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(B, H, d) * 0.3, jnp.float32)
    kp = jnp.asarray(rng.randn(P, K, ps, d) * 0.3, jnp.float32)
    vp = jnp.asarray(rng.randn(P, K, ps, d) * 0.3, jnp.float32)
    tbl = jnp.asarray([[0, 0], [1, 2]], jnp.int32)
    pos = jnp.asarray([0, 0], jnp.int32)
    start = jnp.asarray([1, 0], jnp.int32)  # slot 0: start > pos -> empty
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, interpret=True)
    assert np.all(np.asarray(got[0]) == 0.0)
    G = H // K
    want1 = np.asarray(vp[1, :, 0])  # [K, d]: page 1, row 0
    np.testing.assert_allclose(np.asarray(got[1]).reshape(K, G, d),
                               np.broadcast_to(want1[:, None], (K, G, d)),
                               atol=2e-6)
