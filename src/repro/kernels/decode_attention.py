"""Batched single-token flash-decode as a Pallas TPU kernel.

Decode is the serving hot path the paper's MOB/PE dataflow is actually about:
one query row per sequence against a long KV cache, so the op is purely
memory-bound and the win is reading *only the live cache region* exactly
once.  This kernel is the decode-side counterpart of ``flash_attention``:

- the KV cache is streamed in ``bk``-row blocks (the MOB prefetch pipeline),
  with a running max/denominator online-softmax accumulator in VMEM so the
  [H, S] score matrix never materializes (C4 data reuse);
- per-slot ``pos`` (tokens decoded so far) and ``start`` (validity lower
  bound: 0, or ``pos - window + 1`` for sliding-window layers on a linear
  cache) scalars ride in via scalar prefetch and drive in-kernel validity,
  so dead cache rows — the slot's unwritten tail and anything below
  ``start`` — never receive weight;
- for the linear (global-attention) layout, k-blocks entirely outside the
  live ``[start, pos]`` range are skipped outright: their compute is gated
  by ``pl.when`` and their BlockSpec index remaps to a live block (repeat
  visits elide the HBM->VMEM copy), so both score work and cache traffic
  are bounded by the live length, not ``max_len``;
- the ring (sliding-window) layout recovers each entry's absolute row from
  ``pos`` in-kernel (entry ``j`` holds row ``pos - ((pos - j) mod S)``), so
  wrapped caches need no reordering in HBM;
- GQA folds the G query heads that share a kv head into the sublane axis
  (one [G, d] x [d, bk] MXU call per kv head of a block, batched over the
  block's heads — no KV broadcast), and the qk/v head dims may differ
  (MLA's latent-space decode: qk = kvr + rope, v = kvr).

A fully-invalid slot (``start > pos``, e.g. a drained engine slot) returns
exact zeros, mirroring the masked-row contract of ``flash_attention``.

Paged mode (``pages=``): k/v are head-major *page pools* stacked over the
model's layers, ``[L, n_pages, K, page_size, d]``, shared by every sequence;
a scalar-prefetched per-sequence page table ``[B, npp]`` and the index of
the layer to read ride alongside ``pos``/``start``, so the caller's layer
loop never slices a layer's pool out of the stack.  One k-block is one page
of ``Kb`` heads — all the KV heads the kernel sees (the local ones under
tensor parallelism) unless their double-buffered blocks would pass
:data:`KV_VMEM_BUDGET` — so a grid step
streams a whole page of a slot and scores it with head-batched dots.  The
BlockSpec index map follows the table — logical block ``ik`` of slot ``b``
streams page ``pages[b, ik]`` from the pool — so the kernel body is the
linear layout over logical rows; the indirection (layer and page) lives
entirely in the index map, and the same dead-block clipping bounds HBM
traffic by the live length.
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


def _fd_block_live(ik, p_b, s_b, *, bk: int, layout: str):
    """Liveness of k-block ``ik`` for a slot with bounds ``[s_b, p_b]``.

    Linear layout: the block overlaps the live row range.  Ring layout: live
    entries can sit anywhere in the buffer, so every block of a non-drained
    slot is live (there is no dead-block DMA-elision contract for rings).
    Shared between the kernel body (``pl.when``) and the spec builders."""
    if layout == "linear":
        return (ik * bk <= p_b) & (ik * bk + bk > s_b)
    return p_b >= s_b


def fd_dense_spec(B: int, H: int, K: int, S: int, dq: int, dv: int, *,
                  layout: str = "linear", bk: int = 128) -> KernelSpec:
    """Grid/BlockSpec contract of the dense ``flash_decode`` kernel.

    Scalar domains are hostile: ``pos`` reaches ``S`` (a frozen slot whose
    last token filled the cache keeps ``pos == S``) and ``start`` may exceed
    ``pos`` (a drained slot).  The ring layout has ``block_live=None``: its
    slot-level ``pl.when`` gate skips compute but every block's DMA still
    runs, since any entry of a wrapped buffer may be live."""
    G = H // K
    Gp = round_up(G, 8)
    bk_ = min(bk, S)
    if S % bk_:
        divs = [d for d in range(32, bk_ + 1) if S % d == 0 and d % 8 == 0]
        if divs:
            bk_ = max(divs)
    Sp = round_up(S, bk_)
    nk = Sp // bk_

    def q_map(b, kh, ik, *_):
        return (b, kh, 0, 0)

    def kv_map(b, kh, ik, pos_ref, start_ref):
        if layout == "linear":
            # dead k-blocks (outside [start, pos]) revisit a live block
            # index instead: the grid pipeline elides the repeated DMA, so
            # HBM traffic — the cost that dominates decode — is bounded by
            # the live length, not the cache capacity.  The kernel skips
            # their compute (block_live) so the remapped data is never read.
            lo = jnp.minimum(start_ref[b] // bk_, nk - 1)
            hi = jnp.minimum(pos_ref[b] // bk_, nk - 1)  # pos >= S: dropped
            ik = jnp.clip(ik, lo, hi)
        return (b, kh, ik, 0)

    def block_live(b, kh, ik, pos, start):
        return _fd_block_live(ik, pos[b], start[b], bk=bk_, layout=layout)

    src_file, src_line = provenance(kv_map)
    return KernelSpec(
        name=f"flash_decode_{layout}",
        grid=(B, K, nk),
        scalars=(
            ScalarSpec("pos", (B,), 0, S),
            ScalarSpec("start", (B,), 0, S),
        ),
        operands=(
            OperandSpec("q", (1, 1, Gp, dq), q_map, (B, K, 1, 1)),
            OperandSpec("k", (1, 1, bk_, dq), kv_map, (B, K, nk, 1)),
            OperandSpec("v", (1, 1, bk_, dv), kv_map, (B, K, nk, 1)),
            OperandSpec("o", (1, 1, Gp, dv), q_map, (B, K, 1, 1),
                        is_output=True),
        ),
        block_live=block_live if layout == "linear" else None,
        reduction_axes=(2,),
        src_file=src_file, src_line=src_line,
    )


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
        # is elided), exactly like the dense linear layout's clipping
        lo = jnp.minimum(start_ref[b] // ps, npp - 1)
        hi = jnp.minimum(pos_ref[b] // ps, npp - 1)
        ik = jnp.clip(ik, lo, hi)
        return (layer_ref[0], pages_ref[b, ik], kh, 0, 0)

    def block_live(b, kh, ik, pos, start, pages, layer):
        return _fd_block_live(ik, pos[b], start[b], bk=ps, layout="linear")

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


def _fd_kernel(pos_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, nk: int, bk: int, S: int,
               layout: str, softcap: float, scale: float):
    """One (batch-slot, kv-head block, k-block) grid step.

    Blocks carry a leading head axis of ``Kb`` heads (one in the dense
    kernel) scored with head-batched dots; the ``m``/``l``/``acc`` scratch
    is per head.  ``S`` is the unpadded cache capacity; rows ``>= S`` are
    grid padding.  ``pos_ref``/``start_ref`` are the scalar-prefetched
    per-slot validity bounds (cache row of the current token / first live
    row).
    """
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p_b = pos_ref[b]
    s_b = start_ref[b]

    # linear: live rows are exactly [start, pos] — skip blocks fully outside
    # so the streamed score work is bounded by the live length, not S.
    # ring: live entries can sit anywhere, gate only on a drained slot.
    block_live = _fd_block_live(ik, p_b, s_b, bk=bk, layout=layout)

    @pl.when(block_live)
    def _block():
        q = q_ref[0]       # [Kb, Gp, dq]
        k = k_ref[0]       # [Kb, bk, dq]  (head-major [B|P, K, S|ps, d])
        v = v_ref[0]       # [Kb, bk, dv]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=F32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        j = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if layout == "ring":
            # entry j holds absolute row pos - ((pos - j) mod S): the last S
            # writes, with entry (pos mod S) freshly holding row pos
            a = p_b - jnp.mod(p_b - j, S)
            valid = (a >= 0) & (a >= s_b)
        else:
            valid = (j >= s_b) & (j <= p_b)
        valid &= j < S  # grid padding: ragged S rounded up to bk
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


def _fd_kernel_paged(pos_ref, start_ref, pages_ref, layer_ref, *args, **kw):
    """Paged entry: the page table and the layer index are consumed by the
    BlockSpec index map only — the kernel body works in logical rows and
    never sees them."""
    del pages_ref, layer_ref
    _fd_kernel(pos_ref, start_ref, *args, **kw)


def _flash_decode_paged(q, k, v, pos, start, pages, layer, *,
                        softcap: float, scale, dv: int | None,
                        interpret: bool):
    """q: [B, H, dq]; k/v: head-major page pools stacked over layers
    [L, P, K, ps, d]; pages: [B, npp] int32; layer: int32 scalar ->
    [B, H, dv].  Logical row ``r`` of slot ``b`` lives at
    ``pool[layer, pages[b, r // ps], :, r % ps]``; validity is the linear
    rule over logical rows ``[start, pos]``.  ``ps`` must be a multiple of 8
    (sublane alignment — enforced by ``EngineConfig``)."""
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
        functools.partial(_fd_kernel_paged, nk=npp, bk=ps, S=npp * ps,
                          layout="linear", softcap=softcap, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Gp, dv), q.dtype),
        interpret=interpret,
    )(pos, start, pages, layer, qg, k, v)
    return out[:, :, :G].reshape(B, H, dv)


def flash_decode(q, k, v, pos, start=None, *, layout: str = "linear",
                 softcap: float = 0.0, scale=None, bk: int = 128,
                 dv: int | None = None, pages=None, layer=None,
                 interpret: bool = False):
    """q: [B, H, dq]; k: [B, S, K, dq]; v: [B, S, K, >=dv] -> [B, H, dv].

    k/v arrive in the slot-cache layout ``[B, S, K, d]`` and are moved to
    head-major ``[B, K, S, d]`` before the kernel, whose k/v blocks are one
    head's ``[bk, d]`` tile (Mosaic tiles the last two block dims).  The
    serving engine decodes through the paged pools, which are stored
    head-major and need no such copy.  ``pos``/``start``: [B] int32
    per-slot validity bounds (broadcastable scalars accepted; ``start=None``
    means every row from 0 is live).  ``layout`` selects the validity rule:
    ``"linear"`` (global attention, rows ``[start, pos]`` live) or ``"ring"``
    (sliding window of size S, entry ``pos % S`` holding the current token).
    H % K == 0 (GQA).  ``dv`` narrows the value read to the first ``dv``
    columns of ``v`` via the BlockSpec (no slicing copy): MLA passes its
    concatenated ``[latent | k_rope]`` cache as BOTH k and v, with the
    latent (the value) being the first ``kv_lora_rank`` columns.

    ``pages`` switches to the paged cache: k/v become head-major page pools
    stacked over layers, ``[L, n_pages, K, page_size, d]``, read at the
    int32 scalar ``layer`` (one layer's bare ``[n_pages, K, page_size, d]``
    pools with ``layer=None``), and ``pages`` the [B, npp] page table (see
    :func:`_flash_decode_paged`); ``layout`` must be linear/paged — sliding
    windows under paging express validity through ``start``
    (``max(0, pos - window + 1)``), not a ring.
    """
    if pages is not None:
        assert layout in ("linear", "paged"), \
            f"paged decode is linear-validity only, got layout={layout!r}"
        k, v, layer = layer_pools(k, v, layer)
        return _flash_decode_paged(q, k, v, pos, start, pages, layer,
                                   softcap=softcap, scale=scale, dv=dv,
                                   interpret=interpret)
    B, H, dq = q.shape
    S, K = k.shape[1], k.shape[2]
    dv = dv or v.shape[-1]
    G = H // K
    scale = scale if scale is not None else dq ** -0.5
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    start = (jnp.zeros((B,), jnp.int32) if start is None
             else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,)))
    shared = v is k  # MLA dual-operand form: one cache array, two BlockSpecs
    k = k.astype(q.dtype).transpose(0, 2, 1, 3)  # [B, K, S, d]
    v = k if shared else v.astype(q.dtype).transpose(0, 2, 1, 3)

    # sublane-align the per-kv-head query group; padded rows are sliced off
    # (block sizing — the largest sublane-aligned divisor of S when S % bk
    # is awkward — lives in fd_dense_spec so the prover sees the same grid)
    Gp = round_up(G, 8)
    qg = q.reshape(B, K, G, dq)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    spec = fd_dense_spec(B, H, K, S, dq, dv, layout=layout, bk=bk)
    bk_ = spec.operands[1].block_shape[2]
    nk = spec.grid[2]
    Sp = nk * bk_
    if Sp != S:
        pads = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        k = jnp.pad(k, pads)
        v = k if shared else jnp.pad(v, pads)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # pos, start
        grid=spec.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in spec.inputs],
        out_specs=pl.BlockSpec(spec.outputs[0].block_shape,
                               spec.outputs[0].index_map),
        scratch_shapes=[
            pltpu.VMEM((1, Gp, 1), F32),
            pltpu.VMEM((1, Gp, 1), F32),
            pltpu.VMEM((1, Gp, dv), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fd_kernel, nk=nk, bk=bk_, S=S, layout=layout,
                          softcap=softcap, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Gp, dv), q.dtype),
        interpret=interpret,
    )(pos, start, qg, k, v)
    return out[:, :, :G].reshape(B, H, dv)
