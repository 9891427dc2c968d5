"""Cross-pod int8 gradient compression (distributed-optimization trick).

The `pod` mesh axis is pure data parallelism over the (slow, DCN-class)
pod-to-pod links; compressing that all-reduce is the classic bandwidth
optimization.  We all-reduce in int8 with a shared (pmax'd) scale and int32
accumulation: for P pods, bytes-on-wire drop ~4x vs f32 (all-gather int8 +
local sum), with error feedback carrying the quantization residual to the
next step so convergence is unbiased to first order.

Used inside ``shard_map`` over the pod axis (see launch/train.py and the
§Perf collective hillclimb).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def compressed_mean(g, axis_name: str, err=None):
    """Mean of `g` across `axis_name` via int8 all-gather + local int32 sum.

    Returns (mean, new_err).  `err` (same shape as g) is the error-feedback
    residual; pass None to disable."""
    gf = g.astype(F32)
    if err is not None:
        gf = gf + err
    amax = lax.pmax(jnp.max(jnp.abs(gf)), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    n = lax.axis_size(axis_name)
    allq = lax.all_gather(q, axis_name)  # [n, ...] int8 on the wire
    mean = (jnp.sum(allq.astype(jnp.int32), axis=0).astype(F32) * scale) / n
    new_err = gf - q.astype(F32) * scale if err is not None else None
    return mean.astype(g.dtype), new_err


def compressed_tree_mean(grads, axis_name: str, errs=None):
    """Tree-mapped compressed_mean; errs may be None (no error feedback)."""
    if errs is None:
        return jax.tree.map(lambda g: compressed_mean(g, axis_name)[0], grads), None
    pairs = jax.tree.map(lambda g, e: compressed_mean(g, axis_name, e),
                         grads, errs)
    mean = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    err = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return mean, err
