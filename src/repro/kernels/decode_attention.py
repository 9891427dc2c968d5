"""Batched single-token flash-decode over the paged KV pools, as a Pallas
TPU kernel.

Decode is the serving hot path the paper's MOB/PE dataflow is actually about:
one query row per sequence against a long KV cache, so the op is purely
memory-bound and the win is reading *only the live cache region* exactly
once.  This kernel is the decode-side counterpart of ``flash_attention``:

- k/v are head-major *page pools* stacked over the model's layers,
  ``[L, n_pages, K, page_size, d]``, shared by every sequence.  A
  scalar-prefetched per-sequence page table ``[B, npp]`` and the index of
  the layer to read ride alongside ``pos``/``start``, so the caller's layer
  loop never slices a layer's pool out of the stack;
- one k-block is one page of ``Kb`` heads — all the KV heads the kernel
  sees (the local ones under tensor parallelism) unless their
  double-buffered blocks would pass :data:`KV_VMEM_BUDGET` — so a grid step
  streams a whole page of a slot and scores it with head-batched dots, with
  a running max/denominator online-softmax accumulator in VMEM so the
  [H, S] score matrix never materializes (C4 data reuse);
- the BlockSpec index map follows the table — logical block ``ik`` of slot
  ``b`` streams page ``pages[b, ik]`` from the pool — so the kernel body
  works in logical rows; the indirection (layer and page) lives entirely in
  the index map;
- per-slot ``pos`` (row of the current token) and ``start`` (validity lower
  bound: 0, or ``pos - window + 1`` for sliding-window layers) drive
  in-kernel validity, so dead rows — the slot's unwritten tail and anything
  below ``start`` — never receive weight;
- logical pages entirely outside the live ``[start, pos]`` range are
  skipped: their compute is gated by ``pl.when`` and their index map
  revisits a live page (repeat visits elide the HBM->VMEM copy), so both
  score work and cache traffic are bounded by the live length, not
  ``max_len``;
- GQA folds the G query heads that share a kv head into the sublane axis
  (no KV broadcast), and the qk/v head dims may differ (MLA's latent-space
  decode: qk = kvr + rope, v = kvr).

A fully-invalid slot (``start > pos``, e.g. a drained engine slot) returns
exact zeros, mirroring the masked-row contract of ``flash_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import round_up
from repro.kernels.ref import layer_pools
from repro.kernels.spec import KernelSpec, OperandSpec, ScalarSpec, provenance

F32 = jnp.float32
NEG = -1e30
#: VMEM bytes the paged kernel's double-buffered k and v blocks may take
KV_VMEM_BUDGET = 8 << 20


def _fd_block_live(ik, p_b, s_b, *, bk: int):
    """Liveness of logical page ``ik`` (``bk`` rows) for a slot with bounds
    ``[s_b, p_b]``: the page overlaps the live row range.  Shared between
    the kernel body (``pl.when``) and :func:`fd_paged_spec`."""
    return (ik * bk <= p_b) & (ik * bk + bk > s_b)


def fd_paged_heads(K: int, dq: int, dv: int, ps: int, itemsize: int) -> int:
    """KV heads per block of the paged kernel: all ``K`` unless the
    double-buffered k and v blocks would pass :data:`KV_VMEM_BUDGET`, else
    the largest divisor of ``K`` that fits."""
    per_head = 2 * ps * (dq + dv) * itemsize
    return max([kb for kb in range(1, K + 1)
                if K % kb == 0 and kb * per_head <= KV_VMEM_BUDGET] or [1])


def fd_paged_spec(B: int, H: int, K: int, dq: int, dv: int, ps: int,
                  npp: int, n_pages: int, itemsize: int = 2,
                  layers: int = 1) -> KernelSpec:
    """Grid/BlockSpec contract of the paged ``flash_decode`` kernel: one
    grid step per (slot, head block, logical page), a head block being
    :func:`fd_paged_heads` of the ``K`` heads, read from layer ``layer`` of
    ``layers`` stacked pools (the block's layer dim is squeezed)."""
    G = H // K
    Gp = round_up(G, 8)
    S = npp * ps
    Kb = fd_paged_heads(K, dq, dv, ps, itemsize)
    nh = K // Kb

    def q_map(b, kh, ik, *_):
        return (b, kh, 0, 0)

    def kv_map(b, kh, ik, pos_ref, start_ref, pages_ref, layer_ref):
        # dead logical blocks revisit a live page (repeat index -> the DMA
        # is elided), so HBM traffic is bounded by the live length
        lo = jnp.minimum(start_ref[b] // ps, npp - 1)
        hi = jnp.minimum(pos_ref[b] // ps, npp - 1)
        ik = jnp.clip(ik, lo, hi)
        return (layer_ref[0], pages_ref[b, ik], kh, 0, 0)

    def block_live(b, kh, ik, pos, start, pages, layer):
        return _fd_block_live(ik, pos[b], start[b], bk=ps)

    src_file, src_line = provenance(kv_map)
    return KernelSpec(
        name="flash_decode_paged",
        grid=(B, nh, npp),
        scalars=(
            ScalarSpec("pos", (B,), 0, S),
            ScalarSpec("start", (B,), 0, S),
            ScalarSpec("pages", (B, npp), 0, n_pages - 1),
            ScalarSpec("layer", (1,), 0, layers - 1),
        ),
        operands=(
            OperandSpec("q", (1, Kb, Gp, dq), q_map, (B, nh, 1, 1)),
            OperandSpec("k", (None, 1, Kb, ps, dq), kv_map,
                        (layers, n_pages, nh, 1, 1)),
            OperandSpec("v", (None, 1, Kb, ps, dv), kv_map,
                        (layers, n_pages, nh, 1, 1)),
            OperandSpec("o", (1, Kb, Gp, dv), q_map, (B, nh, 1, 1),
                        is_output=True),
        ),
        block_live=block_live,
        reduction_axes=(2,),
        src_file=src_file, src_line=src_line,
    )


def _fd_kernel_paged(pos_ref, start_ref, pages_ref, layer_ref, q_ref, k_ref,
                     v_ref, o_ref, m_ref, l_ref, acc_ref, *, nk: int, bk: int,
                     softcap: float, scale: float):
    """One (batch-slot, kv-head block, logical page) grid step.

    Blocks carry a leading head axis of ``Kb`` heads scored with
    head-batched dots; the ``m``/``l``/``acc`` scratch is per head.
    ``pos_ref``/``start_ref`` are the scalar-prefetched per-slot validity
    bounds (logical row of the current token / first live row); the page
    table and the layer index are consumed by the BlockSpec index map only.
    """
    del pages_ref, layer_ref
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p_b = pos_ref[b]
    s_b = start_ref[b]

    # live rows are exactly [start, pos]: skip pages fully outside so the
    # streamed score work is bounded by the live length, not the table
    @pl.when(_fd_block_live(ik, p_b, s_b, bk=bk))
    def _block():
        q = q_ref[0]       # [Kb, Gp, dq]
        k = k_ref[0]       # [Kb, bk, dq]  (one page of the head-major pool)
        v = v_ref[0]       # [Kb, bk, dv]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=F32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        j = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = (j >= s_b) & (j <= p_b)
        valid &= j < nk * bk  # the table's rows; always true on this grid
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # rows with no valid key keep m_new == NEG, where the update above
        # degenerates to exp(0) == 1 per entry; zero them so l stays 0 and
        # the store emits exact zeros (empty-slot contract).
        p = jnp.where(m_new > NEG * 0.5, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode(q, k, v, pos, start=None, *, pages, layer=None,
                 softcap: float = 0.0, scale=None, dv: int | None = None,
                 interpret: bool = False):
    """q: [B, H, dq]; k/v: head-major page pools stacked over layers
    ``[L, n_pages, K, page_size, d]``, read at the int32 scalar ``layer``
    (one layer's bare ``[n_pages, K, page_size, d]`` pools with
    ``layer=None``); pages: [B, npp] int32 page tables -> [B, H, dv].

    Logical row ``r`` of slot ``b`` lives at
    ``pool[layer, pages[b, r // ps], :, r % ps]``.  ``pos``/``start``: [B]
    int32 per-slot validity bounds (broadcastable scalars accepted;
    ``start=None`` means every row from 0 is live): logical rows
    ``[start, pos]`` are live.  Sliding windows pass
    ``start = max(0, pos - window + 1)``.  H % K == 0 (GQA).  ``dv`` narrows
    the value read to the first ``dv`` columns of ``v`` via the BlockSpec
    (no slicing copy): MLA passes its fused ``[latent | k_rope]`` pool as
    BOTH k and v, with the latent (the value) being the first
    ``kv_lora_rank`` columns.  ``ps`` must be a multiple of 8 (sublane
    alignment — enforced by ``EngineConfig``).
    """
    k, v, layer = layer_pools(k, v, layer)
    B, H, dq = q.shape
    K, ps = k.shape[2], k.shape[3]
    npp = pages.shape[1]
    dv = dv or v.shape[-1]
    G = H // K
    scale = scale if scale is not None else dq ** -0.5
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    start = (jnp.zeros((B,), jnp.int32) if start is None
             else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,)))
    pages = jnp.asarray(pages, jnp.int32)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    shared = v is k  # MLA dual-operand form
    if k.dtype != q.dtype:
        k = k.astype(q.dtype)
    if shared:
        v = k
    elif v.dtype != q.dtype:
        v = v.astype(q.dtype)

    # sublane-align the per-kv-head query group; padded rows are sliced off
    Gp = round_up(G, 8)
    qg = q.reshape(B, K, G, dq)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    spec = fd_paged_spec(B, H, K, dq, dv, ps, npp, k.shape[1],
                         itemsize=q.dtype.itemsize, layers=k.shape[0])
    Kb = spec.operands[1].block_shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # pos, start, pages, layer
        grid=spec.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in spec.inputs],
        out_specs=pl.BlockSpec(spec.outputs[0].block_shape,
                               spec.outputs[0].index_map),
        scratch_shapes=[
            pltpu.VMEM((Kb, Gp, 1), F32),
            pltpu.VMEM((Kb, Gp, 1), F32),
            pltpu.VMEM((Kb, Gp, dv), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fd_kernel_paged, nk=npp, bk=ps, softcap=softcap,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Gp, dv), q.dtype),
        interpret=interpret,
    )(pos, start, pages, layer, qg, k, v)
    return out[:, :, :G].reshape(B, H, dv)
