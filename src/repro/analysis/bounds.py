"""BlockSpec bounds prover (rules K001-K004).

Every Pallas kernel exposes its grid / index-map construction as a
:class:`repro.kernels.spec.KernelSpec`.  The prover enumerates the full grid
(vectorized — all grid points at once as numpy index arrays) against
worst-case scalar-prefetch operands drawn from each ``ScalarSpec``'s hostile
domain, and checks:

K001  every index map returns, for every grid point and scalar combination,
      a block index inside ``[0, grid_blocks[d])`` per dimension, and never
      reads a scalar table out of bounds (table reads go through a guarded
      wrapper — numpy would silently wrap negative indices);
K002  along the innermost grid axis, the number of DMAs (1 + index
      transitions — Pallas elides the copy when consecutive steps map to
      the same block) never exceeds the ``pl.when``-live block count: dead
      blocks must be remapped onto live indices, not merely masked;
K003  output index maps are invariant along the declared reduction axes
      (otherwise partial accumulator states are stored per step);
K004  the last two dims of every block are multiples of the TPU tile
      ``(8, 128)`` or equal the array's dims (``block * grid_blocks``) —
      the rule Mosaic enforces when it lowers the kernel for the chip, which
      interpret mode never checks.
"""
from __future__ import annotations

import itertools
from typing import Any, List, Sequence

import numpy as np

from repro.analysis.findings import Finding
from repro.kernels.spec import KernelSpec, OperandSpec, ScalarSpec


class _GuardedTable:
    """Array wrapper whose ``__getitem__`` bounds-checks every index.

    Index maps read scalar-prefetch operands with computed indices
    (``pages_ref[b, ik]``); numpy would wrap negatives silently and only
    raise past the end.  The guard records any violation and clips so
    evaluation can continue and surface further findings."""

    def __init__(self, name: str, arr: np.ndarray, oob: List[str]):
        self.name = name
        self.arr = arr
        self.oob = oob

    def __getitem__(self, idx: Any) -> np.ndarray:
        parts = idx if isinstance(idx, tuple) else (idx,)
        clipped = []
        for axis, part in enumerate(parts):
            ix = np.asarray(part)
            dim = self.arr.shape[axis]
            if ix.size and (int(ix.min()) < 0 or int(ix.max()) >= dim):
                self.oob.append(
                    f"scalar table '{self.name}' read out of bounds on axis "
                    f"{axis}: index range [{int(ix.min())}, {int(ix.max())}]"
                    f" vs dim {dim}")
            clipped.append(np.clip(ix, 0, dim - 1))
        return self.arr[tuple(clipped)]


def _scalar_candidates(spec: ScalarSpec) -> List[np.ndarray]:
    """Worst-case fills of one scalar operand.  Uniform fills cover the
    domain extremes pointwise; for multi-dim tables two spreads (ascending /
    descending distinct entries) exercise index *transitions* (K002)."""
    lo, hi = spec.lo, spec.hi
    vals = sorted({lo, min(lo + 1, hi), (lo + hi) // 2, max(hi - 1, lo), hi})
    cands = [np.full(spec.shape, v, np.int64) for v in vals]
    if hi > lo and len(spec.shape) > 1:
        span = hi - lo + 1
        flat = np.arange(int(np.prod(spec.shape)), dtype=np.int64)
        cands.append((flat % span + lo).reshape(spec.shape))
        cands.append((flat[::-1] % span + lo).reshape(spec.shape))
    return cands


def _eval_map(op: OperandSpec, grid_ids: Sequence[np.ndarray],
              scalars: Sequence[_GuardedTable]) -> np.ndarray:
    """Index map over every grid point at once -> [n_points, n_dims] int."""
    res = op.index_map(*grid_ids, *scalars)
    n = grid_ids[0].size
    cols = []
    for d, comp in enumerate(res):
        arr = np.asarray(comp)
        if not np.issubdtype(arr.dtype, np.integer):
            raise TypeError(f"index map of '{op.name}' returned non-integer "
                            f"dtype {arr.dtype} for dim {d}")
        cols.append(np.broadcast_to(arr, (n,)).astype(np.int64))
    return np.stack(cols, axis=-1)


#: Mosaic's (sublane, lane) tile of a block's last two dims
TPU_TILE = (8, 128)


def _untileable(op: OperandSpec) -> List[str]:
    """K004 messages for one operand's block shape."""
    out = []
    dims = list(zip(op.block_shape, op.grid_blocks))[-2:]
    for (blk, n), tile in zip(dims, TPU_TILE[-len(dims):]):
        if blk % tile and n != 1:  # one block along the dim = the whole dim
            arr = tuple((b or 1) * g  # None: a squeezed dim of one
                        for b, g in zip(op.block_shape, op.grid_blocks))
            out.append(f"block {tuple(op.block_shape)} of '{op.name}' over "
                       f"array {arr}: block dim {blk} is neither a multiple "
                       f"of {tile} nor the whole dim {blk * n}")
    return out


def check_kernel_spec(spec: KernelSpec, context: str = "") -> List[Finding]:
    """Run K001-K004 over one kernel instantiation."""
    out: List[Finding] = []
    ctx = f"{context} kernel={spec.name}" if context else f"kernel={spec.name}"
    grid_ids = [ix.reshape(-1) for ix in np.indices(spec.grid)]
    n = grid_ids[0].size

    combos = itertools.product(*(_scalar_candidates(s) for s in spec.scalars))
    seen_rules: set = set()  # dedupe identical findings across combos

    def emit(rule: str, msg: str) -> None:
        key = (rule, msg)
        if key not in seen_rules:
            seen_rules.add(key)
            out.append(Finding(rule, msg, ctx, spec.src_file, spec.src_line))

    for op in spec.operands:
        for msg in _untileable(op):
            emit("K004", msg)

    for combo in combos:
        oob: List[str] = []
        tables = [_GuardedTable(s.name, arr, oob)
                  for s, arr in zip(spec.scalars, combo)]
        raw = [t.arr for t in tables]
        per_op: dict = {}
        for op in spec.operands:
            try:
                idx = _eval_map(op, grid_ids, tables)
            except Exception as exc:  # map crashed outright
                emit("K001", f"index map of '{op.name}' failed to evaluate: "
                             f"{type(exc).__name__}: {exc}")
                continue
            per_op[op.name] = (op, idx)
            for d in range(idx.shape[1]):
                lo_d, hi_d = int(idx[:, d].min()), int(idx[:, d].max())
                if lo_d < 0 or hi_d >= op.grid_blocks[d]:
                    emit("K001",
                         f"index map of '{op.name}' returns block index in "
                         f"[{lo_d}, {hi_d}] for dim {d} (valid: [0, "
                         f"{op.grid_blocks[d]}))")
        for msg in oob:
            emit("K001", msg)

        # K002: DMA count vs live count along the innermost grid axis
        if spec.block_live is not None and len(spec.grid) > 1:
            inner = spec.grid[-1]
            live = np.broadcast_to(
                np.asarray(spec.block_live(*grid_ids, *raw), bool), (n,))
            live_rows = live.reshape(-1, inner).sum(axis=1)
            for op, idx in per_op.values():
                if op.is_output:
                    continue  # outputs accumulate in VMEM, stored once
                rows = idx.reshape(-1, inner, idx.shape[1])
                dma = 1 + (rows[:, 1:] != rows[:, :-1]).any(-1).sum(axis=1)
                bound = np.maximum(live_rows, 1)
                if (dma > bound).any():
                    i = int(np.argmax(dma > bound))
                    emit("K002",
                         f"'{op.name}' issues {int(dma[i])} DMAs along the "
                         f"innermost axis of grid row {i} but only "
                         f"{int(live_rows[i])} blocks are live — dead "
                         f"blocks must remap to a live index so the "
                         f"revisit copy is elided")

        # K003: output maps invariant along reduction axes
        for op, idx in per_op.values():
            if not op.is_output or not spec.reduction_axes:
                continue
            cube = idx.reshape(*spec.grid, idx.shape[1])
            for axis in spec.reduction_axes:
                if (cube.max(axis=axis) != cube.min(axis=axis)).any():
                    emit("K003",
                         f"output map of '{op.name}' varies along reduction "
                         f"grid axis {axis} — partial accumulator states "
                         f"would be stored per step")
    return out
