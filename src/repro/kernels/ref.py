"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_pools(k, v, layer):
    """The paged kernels' operands: head-major pools stacked over layers,
    ``[L, n_pages, K, page_size, d]``, and the int32 index of the layer to
    read.  A caller holding one layer's bare ``[n_pages, K, page_size, d]``
    pools passes ``layer=None``: they are read as a stack of one, at layer 0.
    ``v is k`` (MLA's one fused pool) is kept."""
    if layer is None:
        shared = v is k
        k, layer = k[None], 0
        v = k if shared else v[None]
    return k, v, jnp.asarray(layer, jnp.int32)


def gather_pages(pool, pages, layer):
    """Dense ``[B, npp * page_size, K, d]`` view of each sequence's rows in
    layer ``layer`` of stacked head-major page pools
    ``[L, n_pages, K, page_size, d]``; pages: [B, npp].  One gather of the
    table's pages — the layer's pool is never sliced out whole."""
    B, npp = pages.shape
    g = pool[layer, pages]  # [B, npp, K, ps, d]
    return g.transpose(0, 1, 3, 2, 4).reshape(B, npp * pool.shape[3],
                                              pool.shape[2], pool.shape[4])


def block_gemm_ref(a, b, out_dtype=None):
    """C = A @ B with f32 accumulation."""
    out_dtype = out_dtype or a.dtype
    return jnp.matmul(a, b, preferred_element_type=F32).astype(out_dtype)


def block_gemm_int8_ref(a_q, b_q, a_scale, b_scale, out_dtype=F32):
    """int8 x int8 -> int32 accumulate, rescale per-row(a) x per-col(b).

    a_q: [M,K] int8, b_q: [K,N] int8, a_scale: [M,1] f32, b_scale: [1,N] f32.
    """
    acc = jnp.matmul(a_q.astype(jnp.int32), b_q.astype(jnp.int32))
    return (acc.astype(F32) * a_scale * b_scale).astype(out_dtype)


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None,
                        softcap=0.0):
    """q: [B,H,Sq,d], k/v: [B,H,Sk,d] (kv heads already broadcast).
    Causal masking aligns the last query with the last key (``Sq < Sk`` is
    the suffix-prefill pattern: queries continue a cached prefix).
    Fully-masked rows return zeros (matching the Pallas kernel)."""
    B, H, Sq, d = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=F32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos + (Sk - Sq)  # align last query with last key
    if window:
        mask &= kpos > qpos + (Sk - Sq) - window
    mask = jnp.broadcast_to(mask[None], (B, Sq, Sk))
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None], p, 0.0)  # all-masked row -> zeros, not 1/Sk
    # f32 accumulation like the kernel's VMEM accumulator, one final cast
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=F32).astype(v.dtype)


def flash_attention_paged_ref(q, k, v, pages, q_start, k_len, *, window=0,
                              scale=None, softcap=0.0, layer=None):
    """Oracle for ``flash_attention(pages=...)``: a query *chunk* attending
    over a paged past (chunked prefill).  q: [B,H,C,d]; k/v: head-major
    page pools stacked over layers [L, n_pages, K, page_size, d], read at
    ``layer`` (bare [n_pages, K, page_size, d] pools with ``layer=None``;
    H % K == 0, GQA); pages: [B, npp] int32 page tables; q_start/k_len: [B]
    int32 — query row ``i`` of slot ``b`` sits at
    logical position ``q_start[b] + i`` and attends causally over logical
    rows ``[0, k_len[b])`` (which include the chunk's own freshly-written
    keys).  The oracle gathers each slot's pages into a dense
    [B, npp * page_size, K, d] cache and applies the absolute-position
    causal/window mask — the page table is pure indirection.  Query rows
    past the chunk's valid length are the caller's padding; their output is
    unspecified (the engine slices them off)."""
    pages = jnp.asarray(pages, jnp.int32)
    shared = v is k
    k, v, layer = layer_pools(k, v, layer)
    B, H, C, d = q.shape
    K, ps = k.shape[2], k.shape[3]
    npp = pages.shape[1]
    G = H // K
    S = npp * ps
    scale = scale if scale is not None else d ** -0.5
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_len = jnp.broadcast_to(jnp.asarray(k_len, jnp.int32), (B,))
    kb = jnp.repeat(gather_pages(k, pages, layer), G, axis=2)  # [B,S,H,d]
    vb = kb if shared else jnp.repeat(gather_pages(v, pages, layer), G,
                                      axis=2)
    s = jnp.einsum("bhqd,bshd->bhqs", q, kb,
                   preferred_element_type=F32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = q_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # [B,C]
    kpos = jnp.arange(S, dtype=jnp.int32)
    mask = (kpos[None, None, :] < k_len[:, None, None]) & \
           (kpos[None, None, :] <= qpos[:, :, None])
    if window:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None], p, 0.0)  # all-masked row -> zeros
    return jnp.einsum("bhqs,bshd->bhqd", p.astype(vb.dtype), vb,
                      preferred_element_type=F32).astype(vb.dtype)


def flash_decode_ref(q, k, v, pos, start=None, *, pages, layer=None,
                     softcap=0.0, scale=None, dv=None):
    """Oracle for ``flash_decode``: batched single-token decode over paged
    pools.  q: [B,H,dq]; k/v: head-major pools stacked over layers
    [L, n_pages, K, page_size, d], read at ``layer`` (bare
    [n_pages, K, page_size, d] pools with ``layer=None``); pages: [B, npp]
    int32 page tables; pos/start: [B] int32 (broadcastable).  Logical row
    ``r`` of slot ``b`` lives at
    ``(layer, pages[b, r // page_size], :, r % page_size)`` and rows
    ``[start, pos]`` are live.  The oracle gathers each slot's pages into a
    dense [B, npp * page_size, K, d] cache and applies that rule there — the
    page table is pure indirection.  ``dv`` reads only the first dv value
    columns (MLA passes one fused pool as both k and v).  All-invalid slots
    return zeros."""
    pages = jnp.asarray(pages, jnp.int32)
    shared = v is k
    k, v, layer = layer_pools(k, v, layer)
    k = gather_pages(k, pages, layer)
    v = k if shared else gather_pages(v, pages, layer)
    B, H, dq = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else dq ** -0.5
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    start = (jnp.zeros((B,), jnp.int32) if start is None
             else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,)))
    if dv is not None:
        v = v[..., :dv]
    qg = q.reshape(B, K, G, dq)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=F32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    j = jnp.arange(S)[None, :]
    valid = (j >= start[:, None]) & (j <= pos[:, None])
    vm = valid[:, None, None, :]
    s = jnp.where(vm, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(vm, p, 0.0)  # all-invalid slot -> zeros
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=F32).astype(v.dtype)
    return o.reshape(B, H, v.shape[-1])
