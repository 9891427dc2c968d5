"""Flash-decode kernel validation: Pallas (interpret=True) vs the jnp
oracle over paged pools, across validity rules (rows from ``start``, and
sliding windows as ``start = pos - window + 1``), GQA grouping, logit
softcap, mismatched qk/v head dims (MLA latent decode) and mixed per-slot
positions/validity bounds (drained slots, slots at capacity), plus
semantic tests that pin the kernel to the model's ``attend`` over the
unrolled sequence and the all-invalid-slot -> zeros contract.  Page-level
cases (head blocks, layer stacks, the trash page) live in test_paging.py."""
import jax.numpy as jnp
import numpy as np
import pytest
from _oracle import attend_rows
from _prop import given, settings, st  # hypothesis, or deterministic fallback

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.ops import attend_decode
from repro.models.layers import attend

RNG = np.random.RandomState(7)
PS = 8  # rows per page


def _paged(dense, table=None, ps=PS):
    """Dense rows [B, S, K, d] of each slot scattered over head-major pages
    through ``table`` [B, npp] (default: a shuffled set): (pool
    [P, K, ps, d], table).  Page 0 is the trash page; rows past S on a
    slot's last page are zeros."""
    B, S, K, d = dense.shape
    npp = -(-S // ps)
    if table is None:
        table = RNG.permutation(np.arange(1, B * npp + 1)).reshape(B, npp)
    rows = np.zeros((B, npp * ps, K, d), np.float32)
    rows[:, :S] = np.asarray(dense, np.float32)
    pool = np.zeros((B * npp + 1, K, ps, d), np.float32)
    pool[np.asarray(table)] = rows.reshape(B, npp, ps, K, d).transpose(
        0, 1, 3, 2, 4)
    return jnp.asarray(pool, dense.dtype), jnp.asarray(table, jnp.int32)


def _qkv(B, H, K, S, d, dv=None, dtype=jnp.float32):
    """q [B, H, d] and dense k/v rows [B, S, K, d], with their pools and
    the table they share."""
    dv = dv or d
    q = jnp.asarray(RNG.randn(B, H, d) * 0.3, dtype)
    k = jnp.asarray(RNG.randn(B, S, K, d) * 0.3, dtype)
    v = jnp.asarray(RNG.randn(B, S, K, dv) * 0.3, dtype)
    kp, tbl = _paged(k)
    vp, _ = _paged(v, tbl)
    return q, k, v, kp, vp, tbl


# ---------------------------------------------------------------------------
# kernel (interpret) vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,S,d,rule,softcap", [
    (2, 4, 4, 128, 32, "linear", 0.0),
    (3, 8, 2, 96, 32, "linear", 0.0),    # GQA 4:1
    (2, 4, 1, 128, 32, "linear", 30.0),  # MQA + softcap
    (2, 4, 2, 64, 32, "window", 0.0),    # sliding window of 32
    (3, 6, 2, 50, 16, "window", 20.0),   # ragged rows + softcap
])
def test_flash_decode_matches_oracle(B, H, K, S, d, rule, softcap):
    q, k, v, kp, vp, tbl = _qkv(B, H, K, S, d)
    pos = jnp.asarray(RNG.randint(0, S, size=B), jnp.int32)
    start = jnp.asarray(RNG.randint(0, 8, size=B), jnp.int32)
    if rule == "window":
        start = jnp.maximum(start, pos - 32 + 1)
    want = ref.flash_decode_ref(q, kp, vp, pos, start, pages=tbl,
                                softcap=softcap)
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, softcap=softcap,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    dense = attend_rows(q, k, v, pos, start, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=2e-5, rtol=1e-4)


def test_flash_decode_mla_head_dims():
    """qk dim != v dim (weight-absorbed MLA: q=[latent|rope], v=latent)."""
    B, H, S, dqk, dv = 2, 8, 80, 48, 32
    q, _, _, kp, vp, tbl = _qkv(B, H, 1, S, dqk, dv)
    pos = jnp.asarray([11, 79], jnp.int32)
    scale = 0.17  # explicit MLA scale (dn + dr)**-0.5, not dqk**-0.5
    want = ref.flash_decode_ref(q, kp, vp, pos, None, pages=tbl, scale=scale)
    got = flash_decode(q, kp, vp, pos, None, pages=tbl, scale=scale,
                       interpret=True)
    assert got.shape == (B, H, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


def test_flash_decode_fused_kv_operand():
    """The MLA dual-operand form: ONE fused [latent | k_rope] pool passed
    as both k and v, with ``dv`` narrowing the value read to the latent
    columns — must equal passing the latent pool explicitly."""
    B, H, S, kvr, dr = 2, 8, 72, 32, 16
    q = jnp.asarray(RNG.randn(B, H, kvr + dr) * 0.3, jnp.float32)
    kv, tbl = _paged(jnp.asarray(RNG.randn(B, S, 1, kvr + dr) * 0.3,
                                 jnp.float32))
    pos = jnp.asarray([7, 65], jnp.int32)
    start = jnp.asarray([3, 0], jnp.int32)
    got = flash_decode(q, kv, kv, pos, start, pages=tbl, scale=0.11, dv=kvr,
                       interpret=True)
    want = flash_decode(q, kv, kv[..., :kvr], pos, start, pages=tbl,
                        scale=0.11, interpret=True)
    assert got.shape == (B, H, kvr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


def test_flash_decode_mixed_slot_states():
    """One batch, every slot in a different lifecycle state: fresh (pos ==
    start), mid-sequence, at capacity, and fully empty (start > pos, the
    recycled-slot case) — the empty slot must return exact zeros."""
    B, H, K, S, d = 4, 4, 2, 64, 32
    q, k, v, kp, vp, tbl = _qkv(B, H, K, S, d)
    pos = jnp.asarray([5, 30, 63, 0], jnp.int32)
    start = jnp.asarray([5, 2, 0, 10], jnp.int32)
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, interpret=True)
    want = ref.flash_decode_ref(q, kp, vp, pos, start, pages=tbl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    assert np.all(np.asarray(got[3]) == 0.0)  # all-invalid -> exact zeros
    # fresh slot attends exactly its single live row
    G = H // K
    want0 = np.asarray(v[0, 5])  # [K, d]
    np.testing.assert_allclose(np.asarray(got[0]).reshape(K, G, d),
                               np.broadcast_to(want0[:, None], (K, G, d)),
                               atol=2e-6)


def test_flash_decode_drained_slot_zero():
    """A drained slot — table all trash page, start > pos — returns exact
    zeros beside a windowed slot past its window."""
    B, H, K, S, d = 2, 4, 2, 48, 16
    q, _, _, kp, vp, tbl = _qkv(B, H, K, S, d)
    tbl = tbl.at[0].set(0)
    pos = jnp.asarray([0, 40], jnp.int32)
    start = jnp.asarray([1, 40 - 32 + 1], jnp.int32)
    got = flash_decode(q, kp, vp, pos, start, pages=tbl, interpret=True)
    assert np.all(np.asarray(got[0]) == 0.0)
    want = ref.flash_decode_ref(q, kp, vp, pos, start, pages=tbl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_dtypes(dtype):
    B, H, K, S, d = 2, 4, 2, 64, 32
    q, _, _, kp, vp, tbl = _qkv(B, H, K, S, d, dtype=dtype)
    pos = jnp.asarray([10, 50], jnp.int32)
    got = flash_decode(q, kp, vp, pos, None, pages=tbl, interpret=True)
    want = ref.flash_decode_ref(q, kp, vp, pos, None, pages=tbl)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


# ---------------------------------------------------------------------------
# kernel vs full attention over the unrolled sequence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule,S,pos,start", [
    ("linear", 64, 40, 0), ("linear", 64, 40, 7),  # left-pad exclusion
    ("window", 32, 20, 0), ("window", 32, 50, 0),  # inside / past window
    ("window", 32, 50, 30),                        # pads still inside window
])
def test_decode_oracle_matches_unrolled_attend(rule, S, pos, start):
    """flash_decode over the rows a decode wrote, logical row t at page
    ``t // ps`` == `attend` (the model's jnp core) over the unrolled live
    sequence: causal single query at the end, pad rows dropped via
    ``start``; a window of S expressed as ``start >= pos - S + 1`` matches
    ``attend``'s own window mask."""
    H, K, d = 4, 2, 16
    L = pos + 1
    keys = RNG.randn(1, L, K, d).astype(np.float32) * 0.3
    vals = RNG.randn(1, L, K, d).astype(np.float32) * 0.3
    q = jnp.asarray(RNG.randn(1, H, d) * 0.3, jnp.float32)
    kp, tbl = _paged(jnp.asarray(keys))
    vp, _ = _paged(jnp.asarray(vals), tbl)
    window = S if rule == "window" else 0
    lo = max(start, pos + 1 - S) if window else start
    got = flash_decode(q, kp, vp, jnp.int32(pos), jnp.int32(lo), pages=tbl,
                       interpret=True)
    # attend over rows [start, pos], query at position pos, with the
    # window (if any) applied by attend's own mask
    kk = jnp.asarray(keys[:, start:])  # [1, T, K, d]
    vv = jnp.asarray(vals[:, start:])
    p_k = jnp.arange(start, pos + 1)
    want = attend(q[:, None], kk, vv, jnp.asarray([pos]), p_k, causal=True,
                  window=window)  # [1, 1, H, d]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# ops dispatch + property sweep
# ---------------------------------------------------------------------------

def test_attend_decode_mode_dispatch():
    B, H, K, S, d = 2, 4, 2, 48, 16
    q, _, _, kp, vp, tbl = _qkv(B, H, K, S, d)
    pos = jnp.asarray([9, 33], jnp.int32)
    start = jnp.asarray([2, 0], jnp.int32)
    a = attend_decode(q, kp, vp, pos, start, pages=tbl, mode="reference")
    b = attend_decode(q, kp, vp, pos, start, pages=tbl, mode="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-6, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([16, 33, 64]), p=st.integers(0, 80),
       st_=st.integers(0, 12))
def test_prop_flash_decode_any_state(s, p, st_):
    """Any paged slot state: pos inside, at or past the table's rows (a
    frozen slot), start above pos (drained), with and without a window."""
    q, _, _, kp, vp, tbl = _qkv(2, 4, 2, s, 16)
    pos = jnp.asarray([p % s, p], jnp.int32)
    start = jnp.asarray([st_, st_ // 2], jnp.int32)
    for rule, lo in (("linear", start),
                     ("window", jnp.maximum(start, pos - 8 + 1))):
        got = flash_decode(q, kp, vp, pos, lo, pages=tbl, interpret=True)
        want = ref.flash_decode_ref(q, kp, vp, pos, lo, pages=tbl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=1e-5, err_msg=rule)
