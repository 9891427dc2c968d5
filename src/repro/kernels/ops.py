"""jit'd public wrappers around the Pallas kernels, with kernel_mode dispatch
(reference | interpret | pallas) and a custom VJP for the block GEMM so the
kernel path is trainable."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.block_gemm import block_gemm, block_gemm_int8
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def cgra_matmul(a, b, mode: str = "reference", out_dtype=None):
    """C = A @ B through the CGRA block-GEMM path.

    ``out_dtype`` requests the epilogue's store dtype: the f32 accumulator
    is cast exactly once, so callers that need full-precision outputs (the
    logits head) avoid an f32 -> compute-dtype -> f32 round trip."""
    if mode == "reference":
        return ref.block_gemm_ref(a, b, out_dtype=out_dtype)
    return block_gemm(a, b, out_dtype=out_dtype,
                      interpret=(mode == "interpret"))


def _mm_fwd(a, b, mode, out_dtype):
    return cgra_matmul(a, b, mode, out_dtype), (a, b)


def _mm_bwd(mode, out_dtype, res, g):
    a, b = res
    ga = cgra_matmul(g.astype(b.dtype), b.T, mode).astype(a.dtype)
    gb = cgra_matmul(a.T, g.astype(a.dtype), mode).astype(b.dtype)
    return ga, gb


cgra_matmul.defvjp(_mm_fwd, _mm_bwd)


def cgra_matmul_int8(a_q, b_q, a_scale, b_scale, mode: str = "reference",
                     out_dtype=jnp.float32):
    """Packed int8 GEMM with fused per-row/per-col dequant (inference)."""
    if mode == "reference":
        return ref.block_gemm_int8_ref(a_q, b_q, a_scale, b_scale, out_dtype)
    return block_gemm_int8(a_q, b_q, a_scale, b_scale,
                           interpret=(mode == "interpret"), out_dtype=out_dtype)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              pages=None, q_start=None, k_len=None, layer=None,
              mode: str = "reference", bq=128, bk=128):
    """q: [B,H,Sq,d]; k/v: [B,K,Sk,d] (GQA: H % K == 0).  Ragged Sq/Sk ok;
    causal masking aligns the last query with the last key (``Sq < Sk`` is
    the suffix-prefill pattern over a cached prefix).

    ``pages`` ([B, npp] int32) switches to the chunked-prefill *paged past*
    layout: k/v become head-major page pools stacked over layers
    ``[L, n_pages, K, page_size, d]`` read at the int32 scalar ``layer``,
    and ``q_start``/``k_len`` [B] place the query chunk at absolute
    positions ``q_start + i`` attending over logical rows ``[0, k_len)``."""
    if pages is not None:
        if mode == "reference":
            return ref.flash_attention_paged_ref(q, k, v, pages, q_start,
                                                 k_len, window=window,
                                                 softcap=softcap, layer=layer)
        return flash_attention(q, k, v, pages=pages, q_start=q_start,
                               k_len=k_len, layer=layer, window=window,
                               softcap=softcap, bq=bq,
                               interpret=(mode == "interpret"))
    if mode == "reference":
        G = q.shape[1] // k.shape[1]
        kb = jnp.repeat(k, G, axis=1)
        vb = jnp.repeat(v, G, axis=1)
        return ref.flash_attention_ref(q, kb, vb, causal=causal, window=window,
                                       softcap=softcap)
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, bq=bq, bk=bk,
                           interpret=(mode == "interpret"))


def attend_decode(q, k, v, pos, start=None, *, pages, layer=None,
                  softcap=0.0, scale=None, dv=None, mode: str = "reference"):
    """Batched single-token decode over the paged KV pools.

    q: [B,H,dq]; k/v: head-major page pools stacked over layers
    ``[L, n_pages, K, page_size, d]``, indirected through the [B, npp]
    int32 page tables ``pages`` and read at the int32 scalar ``layer``
    -> [B,H,dv].  ``pos``/``start`` are the per-slot [B] validity bounds
    (logical rows ``[start, pos]`` are live).  ``dv`` narrows the value read
    to the first dv columns — MLA latent decode passes its fused
    ``[latent | k_rope]`` pool as both k and v.
    """
    if mode == "reference":
        return ref.flash_decode_ref(q, k, v, pos, start, pages=pages,
                                    layer=layer, softcap=softcap, scale=scale,
                                    dv=dv)
    return flash_decode(q, k, v, pos, start, pages=pages, layer=layer,
                        softcap=softcap, scale=scale, dv=dv,
                        interpret=(mode == "interpret"))
