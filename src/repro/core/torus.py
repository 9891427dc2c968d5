"""Switchless-torus collective schedules (paper claim C3, pod scale).

TPU ICI *is* a switchless torus; the paper's insight — schedule data movement
as neighbor-only hops that overlap with compute — maps onto
``lax.ppermute`` ring schedules inside ``shard_map``.  These replace XLA's
monolithic all-gather / all-reduce with tp-1 neighbor permutes, each
overlappable with the partial GEMM it feeds (the MOB decoupling, C2, at pod
scale).

All functions are written to run *inside* ``shard_map`` over ``axis_name``.
``tests/test_torus.py`` validates them against dense references on a fake
8-device mesh; ``benchmarks/interconnect.py`` compares the lowered HLO
collective schedule against the XLA default.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _ring_perm(axis_name, shift=1):
    n = lax.axis_size(axis_name)
    return [(i, (i + shift) % n) for i in range(n)]


def ring_allgather_matmul(x_shard, w_local, axis_name="model"):
    """Y = X @ W, X sharded over rows (tokens), W sharded over cols.

    x_shard: [Tl, D] (this device's token chunk), w_local: [D, Fl].
    Returns Y_full_rows: [tp*Tl, Fl] — every token row, local feature shard.

    Instead of all-gather(X) followed by one big GEMM, the torus schedule
    rotates token chunks around the ring: at step s the device multiplies the
    chunk it currently holds while the next chunk is in flight on the
    neighbor link (overlap).  Bytes on the wire equal the all-gather, but
    every transfer is a single switchless neighbor hop.
    """
    tp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    Tl, D = x_shard.shape
    Fl = w_local.shape[1]
    out = jnp.zeros((tp * Tl, Fl), w_local.dtype)
    cur = x_shard
    perm = _ring_perm(axis_name)
    for s in range(tp):
        part = jnp.matmul(cur, w_local)  # [Tl, Fl]
        src = (idx - s) % tp  # whose chunk we just multiplied (perm i -> i+1)
        out = lax.dynamic_update_slice(out, part.astype(out.dtype), (src * Tl, 0))
        if s < tp - 1:
            cur = lax.ppermute(cur, axis_name, perm)
    return out


def matmul_reducescatter_ring(h_full, w_local, axis_name="model"):
    """Y_shard = reduce_scatter_rows( H @ W_partial ).

    h_full: [T, Fl] (local feature shard of all tokens), w_local: [Fl, D].
    Returns: [T/tp, D] — this device's token chunk of the summed output.

    Ring reduce-scatter: the accumulator for token chunk c travels the ring,
    gathering each device's partial GEMM for that chunk — tp-1 neighbor hops,
    each overlapped with the next partial GEMM.
    """
    tp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    T, Fl = h_full.shape
    Tl = T // tp
    perm = _ring_perm(axis_name, shift=1)

    def chunk_mm(c):
        hc = lax.dynamic_slice(h_full, (c * Tl, 0), (Tl, Fl))
        return jnp.matmul(hc, w_local)  # [Tl, D]

    # the accumulator that ends on device i starts at device i+1 carrying
    # chunk i; a device visited at hop s therefore adds chunk (idx - s - 1)
    acc = chunk_mm((idx - 1) % tp)
    for s in range(1, tp):
        acc = lax.ppermute(acc, axis_name, perm)
        acc = acc + chunk_mm((idx - s - 1) % tp)
    return acc  # == sum over devices of chunk `idx`


def ring_allreduce(x, axis_name="model"):
    """Bidirectional-ring all-reduce via ppermute (reduce-scatter + all-gather
    on flattened chunks).  Used where we want the collective expressed as
    neighbor hops (e.g. to prove C3 schedules) rather than XLA's all-reduce."""
    tp = lax.axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % tp
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(tp, -1)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(axis_name)

    # reduce-scatter
    acc = jnp.take(chunks, (idx - 1) % tp, axis=0)
    for s in range(1, tp):
        acc = lax.ppermute(acc, axis_name, perm)
        acc = acc + jnp.take(chunks, (idx - s - 1) % tp, axis=0)
    # all-gather
    out = jnp.zeros_like(chunks)
    cur = acc
    for s in range(tp):
        src = (idx - s) % tp
        out = jnp.where(jnp.arange(tp)[:, None] == src, cur[None], out)
        if s < tp - 1:
            cur = lax.ppermute(cur, axis_name, perm)
    res = out.reshape(-1)
    if pad:
        res = res[:-pad]
    return res.reshape(x.shape)


# ---------------------------------------------------------------------------
# Drop-in torus tensor-parallel FFN (sequence-parallel in, sequence-parallel
# out).  Used by the perf hillclimb via cfg.use_torus_tp.
# ---------------------------------------------------------------------------

def torus_ffn(x, w_gate, w_up, w_down, mesh: Mesh, axis_name="model",
              act=jax.nn.silu):
    """x: [B, S, D] (replicated over `axis_name`); weights sharded on the ffn
    dim.  Computes SwiGLU FFN with ring-scheduled collectives only."""

    def inner(xs, wg, wu, wd):
        B, Sl, D = xs.shape
        xf = xs.reshape(B * Sl, D)
        g = ring_allgather_matmul(xf, wg, axis_name)
        u = ring_allgather_matmul(xf, wu, axis_name)
        h = act(g) * u  # [B*S, Fl]
        y = matmul_reducescatter_ring(h, wd, axis_name)  # [B*Sl, D]
        return y.reshape(B, Sl, D)

    spec_x = P(None, axis_name, None)
    spec_w_col = P(None, axis_name)
    spec_w_row = P(axis_name, None)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(spec_x, spec_w_col, spec_w_col, spec_w_row),
                   out_specs=spec_x)
    return fn(x, w_gate, w_up, w_down)
