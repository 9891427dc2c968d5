"""Introspectable kernel contracts.

Every Pallas kernel in this package builds its grid / BlockSpec geometry
through a :class:`KernelSpec` so that the same index maps and block-liveness
predicates that drive ``pl.pallas_call`` can be enumerated and *proven*
in-bounds by ``repro.analysis`` without duplicating any index arithmetic.

A ``KernelSpec`` is pure data plus plain callables: the index maps take the
grid indices followed by one array per scalar-prefetch operand (mirroring
Pallas' calling convention for ``PrefetchScalarGridSpec`` index maps), and
``block_live`` — when present — is the same predicate the kernel body feeds
to ``pl.when`` to skip dead blocks.  ``ScalarSpec`` declares the worst-case
domain of each scalar operand (page-table entries, ``pos``/``start``/
``k_len`` extremes) that the bounds prover enumerates.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ScalarSpec:
    """Worst-case domain of one scalar-prefetch operand.

    ``lo``/``hi`` are *inclusive* elementwise bounds.  They are deliberately
    hostile: they cover every value the public kernel API accepts, not just
    what the engine produces (e.g. ``pos == max_len`` for frozen slots,
    ``k_len == 0`` for an empty chunk).
    """

    name: str
    shape: Tuple[int, ...]
    lo: int
    hi: int


@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """One blocked operand (input or output) of a kernel.

    ``grid_blocks`` is the number of valid blocks per array dimension, i.e.
    ``padded_dim_size // block_shape[d]`` — the index map must return a block
    index in ``[0, grid_blocks[d])`` for every dimension ``d``.  A
    ``block_shape`` entry of ``None`` is a squeezed dim (Pallas' own
    convention): a block of one, absent from the kernel's ref.
    """

    name: str
    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[Any, ...]]
    grid_blocks: Tuple[int, ...]
    is_output: bool = False


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Grid + BlockSpec contract of one Pallas kernel instantiation.

    ``block_live(*grid_ids, *scalar_arrays) -> bool`` must match the
    ``pl.when`` predicate used inside the kernel body; ``None`` means the
    kernel visits every block.  ``reduction_axes`` are the grid axes along
    which output blocks are revisited (accumulated in VMEM) — the output
    index map must be invariant along them.
    """

    name: str
    grid: Tuple[int, ...]
    scalars: Tuple[ScalarSpec, ...]
    operands: Tuple[OperandSpec, ...]
    block_live: Optional[Callable[..., Any]] = None
    reduction_axes: Tuple[int, ...] = ()
    src_file: str = ""
    src_line: int = 0

    @property
    def outputs(self) -> Tuple[OperandSpec, ...]:
        return tuple(op for op in self.operands if op.is_output)

    @property
    def inputs(self) -> Tuple[OperandSpec, ...]:
        return tuple(op for op in self.operands if not op.is_output)


def provenance(fn: Callable[..., Any]) -> Tuple[str, int]:
    """(file, line) of a callable, for finding reports."""
    code = getattr(fn, "__code__", None)
    if code is None:  # functools.partial etc.
        inner = getattr(fn, "func", None)
        code = getattr(inner, "__code__", None)
    if code is None:
        return "<unknown>", 0
    return code.co_filename, code.co_firstlineno
