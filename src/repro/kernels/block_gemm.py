"""CGRA-style block-wise GEMM as a Pallas TPU kernel (paper claims C1/C2/C4).

The mapping from the paper's 4x4 edge array to the TPU (DESIGN.md §2):

- the PE array's output-stationary sub-matrix blocking -> BlockSpec tiles
  (bm x bn) output blocks accumulated over a bk-strided K grid in a VMEM
  scratch accumulator (f32 / int32);
- the 4x2 MOB LOAD/STORE decoupling -> the pallas_call grid pipeline, which
  double-buffers the HBM->VMEM block copies of A and B ahead of the MXU
  (Pallas emits exactly the decoupled address-generation/DMA the MOBs
  implement in silicon);
- the "packed-data dot product" -> the int8 variant (int8 x int8 -> int32)
  with per-row/per-col rescale fused into the epilogue.

Block shapes come from ``repro.core.cgra.select_block_shapes`` — the same
mapper that places blocks on the 4x4 array, re-parameterized for VMEM/MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cgra import select_block_shapes
from repro.kernels.spec import KernelSpec, OperandSpec, provenance

F32 = jnp.float32


def gemm_spec(M: int, K: int, N: int, *, block_shape=None,
              dtype_bytes: int = 4, out_bytes: int = 4,
              int8: bool = False) -> KernelSpec:
    """Grid/BlockSpec contract of ``block_gemm`` / ``block_gemm_int8``.
    ``dtype_bytes``/``out_bytes``: operand and output element sizes, which
    size the blocks against the VMEM budget."""
    if block_shape is None:
        block_shape = select_block_shapes(M, K, N, dtype_bytes=dtype_bytes,
                                          out_bytes=out_bytes)
    bm, bk, bn = block_shape
    Mp, Kp, Np = (-(-M // bm)) * bm, (-(-K // bk)) * bk, (-(-N // bn)) * bn
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk

    def a_map(i, j, k):
        return (i, k)

    def b_map(i, j, k):
        return (k, j)

    def o_map(i, j, k):
        return (i, j)

    operands = [
        OperandSpec("a", (bm, bk), a_map, (nm, nk)),
        OperandSpec("b", (bk, bn), b_map, (nk, nn)),
    ]
    if int8:
        operands += [
            OperandSpec("a_scale", (bm, 1), lambda i, j, k: (i, 0), (nm, 1)),
            OperandSpec("b_scale", (1, bn), lambda i, j, k: (0, j), (1, nn)),
        ]
    operands.append(OperandSpec("o", (bm, bn), o_map, (nm, nn),
                                is_output=True))
    src_file, src_line = provenance(a_map)
    return KernelSpec(
        name="block_gemm_int8" if int8 else "block_gemm",
        grid=(nm, nn, nk),
        scalars=(),
        operands=tuple(operands),
        block_live=None,  # dense GEMM: every block is live
        reduction_axes=(2,),
        src_file=src_file, src_line=src_line,
    )


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...], preferred_element_type=F32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def block_gemm(a, b, *, block_shape=None, out_dtype=None, interpret=False):
    """C = A[M,K] @ B[K,N], output-stationary block accumulation.

    Arbitrary shapes are padded up to the block grid (the CGRA handles
    ragged edges the same way: partial blocks run at lower utilization).
    """
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    spec = gemm_spec(M, K, N, block_shape=block_shape,
                     dtype_bytes=a.dtype.itemsize,
                     out_bytes=out_dtype.itemsize)
    bm, bk, bn = (spec.operands[0].block_shape[0],
                  spec.operands[0].block_shape[1],
                  spec.operands[1].block_shape[1])
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    Mp, Np = spec.grid[0] * bm, spec.grid[1] * bn
    nk = spec.grid[2]
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk),
        grid=spec.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in spec.inputs],
        out_specs=pl.BlockSpec(spec.outputs[0].block_shape,
                               spec.outputs[0].index_map),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
        interpret=interpret,
    )(ap, bp)
    return out[:M, :N]


def _gemm_int8_kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        a_ref[...], b_ref[...],
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _store():  # fused dequant epilogue: per-row x per-col scales
        o_ref[...] = (acc_ref[...].astype(F32) * sa_ref[...] * sb_ref[...]
                      ).astype(o_ref.dtype)


def block_gemm_int8(a_q, b_q, a_scale, b_scale, *, block_shape=None,
                    out_dtype=F32, interpret=False):
    """Packed-data GEMM: int8 operands, int32 accumulate, fused rescale.

    a_q: [M,K] int8; b_q: [K,N] int8; a_scale: [M,1] f32; b_scale: [1,N] f32.
    """
    M, K = a_q.shape
    N = b_q.shape[1]
    spec = gemm_spec(M, K, N, block_shape=block_shape, dtype_bytes=1,
                     out_bytes=jnp.dtype(out_dtype).itemsize, int8=True)
    bm, bk, bn = (spec.operands[0].block_shape[0],
                  spec.operands[0].block_shape[1],
                  spec.operands[1].block_shape[1])
    ap = _pad_to(a_q, bm, bk)
    bp = _pad_to(b_q, bk, bn)
    sa = _pad_to(a_scale.astype(F32), bm, 1)
    sb = _pad_to(b_scale.astype(F32), 1, bn)
    Mp, Np = spec.grid[0] * bm, spec.grid[1] * bn
    nk = spec.grid[2]
    out = pl.pallas_call(
        functools.partial(_gemm_int8_kernel, nk=nk),
        grid=spec.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in spec.inputs],
        out_specs=pl.BlockSpec(spec.outputs[0].block_shape,
                               spec.outputs[0].index_map),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(ap, bp, sa, sb)
    return out[:M, :N]
