"""Flash attention as a Pallas TPU kernel (online softmax, VMEM-blocked).

Block-wise attention is the paper's C1/C4 applied to the attention GEMM pair:
the (bq x bk) score tile never leaves VMEM, the running max/denominator are
the output-stationary accumulator state, and the KV block streaming is the
MOB prefetch pipeline.  Supports causal masking, sliding windows (Gemma-3
local layers), logit softcapping (Gemma-3 global layers) and GQA via
index-map head folding (no KV broadcast in HBM).  Ragged sequence lengths
are padded up to the block grid (padded keys masked, padded query rows
sliced off) the same way ``block_gemm`` pads ragged GEMMs.
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


def _paged_block_live(iq, ik, qs, kl, *, bq: int, ps: int):
    """Liveness of page ``ik`` for q-block ``iq``: the page holds valid rows
    and is not entirely past the block's causal horizon.  Shared between the
    kernel body (``pl.when``) and :func:`fa_paged_spec` (bounds prover)."""
    return (ik * ps < kl) & (ik * ps <= qs + (iq + 1) * bq - 1)


def fa_dense_spec(B: int, H: int, K: int, Sq: int, Sk: int, d: int, *,
                  bq: int = 128, bk: int = 128) -> KernelSpec:
    """Grid/BlockSpec contract of the dense ``flash_attention`` kernel."""
    G = H // K
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    Sqp, Skp = round_up(Sq, bq_), round_up(Sk, bk_)
    nk = Skp // bk_

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        return ((bh // H) * K + (bh % H) // G, ik, 0)

    src_file, src_line = provenance(kv_map)
    return KernelSpec(
        name="flash_attention",
        grid=(B * H, Sqp // bq_, nk),
        scalars=(),
        operands=(
            OperandSpec("q", (1, bq_, d), q_map, (B * H, Sqp // bq_, 1)),
            OperandSpec("k", (1, bk_, d), kv_map, (B * K, nk, 1)),
            OperandSpec("v", (1, bk_, d), kv_map, (B * K, nk, 1)),
            OperandSpec("o", (1, bq_, d), q_map, (B * H, Sqp // bq_, 1),
                        is_output=True),
        ),
        block_live=None,  # every (q-block, k-block) pair is visited
        reduction_axes=(2,),
        src_file=src_file, src_line=src_line,
    )


def fa_paged_spec(B: int, H: int, K: int, C: int, d: int, ps: int, npp: int,
                  n_pages: int, *, bq: int = 128,
                  layers: int = 1) -> KernelSpec:
    """Grid/BlockSpec contract of the paged chunk-prefill attention kernel,
    reading layer ``layer`` of ``layers`` stacked pools (the block's layer
    dim is squeezed).

    Scalar domains are hostile: ``q_start``/``k_len`` range over the full
    logical capacity (including ``k_len == 0`` — an empty chunk — and
    ``q_start == npp * ps``), and page-table entries over every pool page.
    """
    G = H // K
    bq_ = min(bq, round_up(C, 8))
    Cp = round_up(C, bq_)
    S = npp * ps

    def q_map(b, h, iq, ik, *_):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik, qstart_ref, klen_ref, pages_ref, layer_ref):
        # dead logical pages revisit a live one (repeat index -> the DMA is
        # elided); the kernel gates their compute via _paged_block_live
        hi_k = (klen_ref[b] - 1) // ps
        hi_c = (qstart_ref[b] + (iq + 1) * bq_ - 1) // ps
        hi = jnp.clip(jnp.minimum(hi_k, hi_c), 0, npp - 1)
        ik = jnp.minimum(ik, hi)
        return (layer_ref[0], pages_ref[b, ik], h // G, 0, 0)

    def block_live(b, h, iq, ik, qstart, klen, pages, layer):
        return _paged_block_live(iq, ik, qstart[b], klen[b], bq=bq_, ps=ps)

    src_file, src_line = provenance(kv_map)
    return KernelSpec(
        name="flash_attention_paged",
        grid=(B, H, Cp // bq_, npp),
        scalars=(
            ScalarSpec("q_start", (B,), 0, S),
            ScalarSpec("k_len", (B,), 0, S),
            ScalarSpec("pages", (B, npp), 0, n_pages - 1),
            ScalarSpec("layer", (1,), 0, layers - 1),
        ),
        operands=(
            OperandSpec("q", (1, 1, bq_, d), q_map, (B, H, Cp // bq_, 1)),
            OperandSpec("k", (None, 1, 1, ps, d), kv_map,
                        (layers, n_pages, K, 1, 1)),
            OperandSpec("v", (None, 1, 1, ps, d), kv_map,
                        (layers, n_pages, K, 1, 1)),
            OperandSpec("o", (1, 1, bq_, d), q_map, (B, H, Cp // bq_, 1),
                        is_output=True),
        ),
        block_live=block_live,
        reduction_axes=(3,),
        src_file=src_file, src_line=src_line,
    )


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               nk: int, bq: int, bk: int, sq: int, sk: int, H: int,
               scale: float, causal: bool, window: int, softcap: float):
    """One (batch*head, q-block, k-block) grid step.

    ``sq``/``sk`` are the *unpadded* sequence lengths: the query-position
    offset aligns the last real query with the last real key (``sq < sk``
    is the suffix-prefill pattern — queries continue a cached prefix), and
    key columns at ``kpos >= sk`` are grid padding that must never receive
    weight.
    """
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # [bq, bk]
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    iq = pl.program_id(1)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk  # grid padding: ragged Sk rounded up to bk
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # rows with every key masked so far keep m_new == NEG, where the update
    # above degenerates to exp(0) == 1 per masked entry (mean(V) instead of
    # zeros); zero their probabilities so l stays 0 and the store emits 0.
    p = jnp.where(m_new > NEG * 0.5, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _fa_kernel_paged(qstart_ref, klen_ref, pages_ref, layer_ref, q_ref, k_ref,
                     v_ref, o_ref, m_ref, l_ref, acc_ref, *, nk: int, bq: int,
                     ps: int, window: int, softcap: float, scale: float):
    """One (batch, head, q-block, page) grid step of chunked-prefill
    attention over a paged past.

    The query chunk's rows sit at absolute logical positions
    ``qstart[b] + i`` and attend causally over logical rows
    ``[0, klen[b])`` of the page pool — which include the chunk's own keys,
    written through the page table before the kernel runs.  The page table
    and the layer index are consumed only by the BlockSpec index map
    (``pages_ref``/``layer_ref`` never appear here): the kernel body works
    in logical rows, exactly like the dense ``_fa_kernel``, with validity
    from the prefetched scalars instead of a suffix-alignment offset."""
    del pages_ref, layer_ref
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs = qstart_ref[b]
    kl = klen_ref[b]
    # skip pages past the valid rows or past this q-block's causal horizon;
    # their DMA was already elided by the index-map clip, never read them.
    block_live = _paged_block_live(iq, ik, qs, kl, bq=bq, ps=ps)

    @pl.when(block_live)
    def _block():
        q = q_ref[0, 0]       # [bq, d]
        k = k_ref[0, 0]       # [ps, d]  (head-major pool [P, K, ps, d])
        v = v_ref[0, 0]       # [ps, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = qs + iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ik * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kpos < kl) & (kpos <= qpos)
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > NEG * 0.5, p, 0.0)  # all-masked rows stay zero
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _flash_attention_paged(q, k, v, pages, q_start, k_len, layer, *,
                           window: int, softcap: float, scale, bq: int,
                           interpret: bool):
    """q: [B, H, C, d] query chunk; k/v: head-major page pools stacked over
    layers [L, P, K, ps, d]; pages: [B, npp] int32; layer: int32 scalar ->
    [B, H, C, d].

    Chunked-prefill attention: logical row ``r`` of slot ``b`` lives at
    ``pool[layer, pages[b, r // ps], :, r % ps]``; query row ``i`` sits at
    logical position ``q_start[b] + i`` and rows ``[0, k_len[b])`` are
    valid.  One
    k-block is one page and the BlockSpec index map follows the
    scalar-prefetched table (the ``flash_decode`` paged trick): dead pages —
    beyond the valid rows or beyond the q-block's causal horizon — are
    remapped to a live page index so the repeated-visit DMA is elided, and
    their compute is skipped in-kernel."""
    B, H, C, d = q.shape
    K, ps = k.shape[2], k.shape[3]
    npp = pages.shape[1]
    scale = scale if scale is not None else d ** -0.5
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_len = jnp.broadcast_to(jnp.asarray(k_len, jnp.int32), (B,))
    pages = jnp.asarray(pages, jnp.int32)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    if k.dtype != q.dtype:  # serving pools share the compute dtype: no-op
        k = k.astype(q.dtype)
    if v.dtype != q.dtype:
        v = v.astype(q.dtype)

    spec = fa_paged_spec(B, H, K, C, d, ps, npp, k.shape[1], bq=bq,
                         layers=k.shape[0])
    bq_ = spec.outputs[0].block_shape[2]
    Cp = spec.grid[2] * bq_
    if Cp != C:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Cp - C), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # q_start, k_len, pages, layer
        grid=spec.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in spec.inputs],
        out_specs=pl.BlockSpec(spec.outputs[0].block_shape,
                               spec.outputs[0].index_map),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), F32),
            pltpu.VMEM((bq_, 1), F32),
            pltpu.VMEM((bq_, d), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fa_kernel_paged, nk=npp, bq=bq_, ps=ps,
                          window=window, softcap=softcap, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Cp, d), q.dtype),
        interpret=interpret,
    )(q_start, k_len, pages, layer, q, k, v)
    return out[:, :, :C]


def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                    scale=None, softcap=0.0, pages=None, q_start=None,
                    k_len=None, layer=None, interpret=False):
    """q: [B,H,Sq,d]; k/v: [B,K,Sk,d] with H % K == 0 (GQA folded in the
    BlockSpec index map).  Arbitrary Sq/Sk: ragged shapes are padded up to
    the block grid and sliced back (padded keys are masked out in-kernel).
    Fully-masked rows return zeros.

    ``pages`` switches to the *paged past* layout for chunked prefill: k/v
    become head-major page pools stacked over layers,
    ``[L, n_pages, K, page_size, d]``, read at the int32 scalar ``layer``
    (one layer's bare ``[n_pages, K, page_size, d]`` pools with
    ``layer=None``), ``pages`` the [B, npp] page table, and
    ``q_start``/``k_len`` [B] give the chunk's first query position and the
    valid logical row count (see :func:`_flash_attention_paged`).  Paged
    attention is causal by definition — the chunk continues a causal
    prefix."""
    if pages is not None:
        assert causal, "paged chunk-prefill attention is causal by definition"
        k, v, layer = layer_pools(k, v, layer)
        return _flash_attention_paged(q, k, v, pages, q_start, k_len, layer,
                                      window=window, softcap=softcap,
                                      scale=scale, bq=bq, interpret=interpret)
    B, H, Sq, d = q.shape
    K = k.shape[1]
    Sk = k.shape[2]
    spec = fa_dense_spec(B, H, K, Sq, Sk, d, bq=bq, bk=bk)
    bq_ = spec.operands[0].block_shape[1]
    bk_ = spec.operands[1].block_shape[1]
    Sqp, Skp = spec.grid[1] * bq_, spec.grid[2] * bk_
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
    scale = scale if scale is not None else d ** -0.5
    qf = q.reshape(B * H, Sqp, d)
    kf = k.reshape(B * K, Skp, d)
    vf = v.reshape(B * K, Skp, d)
    nk = spec.grid[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=spec.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in spec.inputs],
        out_specs=pl.BlockSpec(spec.outputs[0].block_shape,
                               spec.outputs[0].index_map),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), F32),
            pltpu.VMEM((bq_, 1), F32),
            pltpu.VMEM((bq_, d), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fa_kernel, nk=nk, bq=bq_, bk=bk_, sq=Sq, sk=Sk,
                          H=H, scale=scale, causal=causal, window=window,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sqp, d)[:, :, :Sq]
