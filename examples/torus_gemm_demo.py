"""Distributed torus-scheduled GEMM demo (paper C3 at pod scale): the FFN of
a transformer layer computed with neighbor-only collective_permute rings
over every device of the process, validated against the dense result, with
the lowered collective schedule printed.  On a TPU host the ring spans its
chips; on CPU, force host devices when starting the process:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/torus_gemm_demo.py
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import torus


def main():
    mesh = jax.make_mesh((jax.device_count(),), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    B, S, D, F = 2, 64, 256, 1024
    x = jnp.asarray(rng.randn(B, S, D), jnp.float32)
    wg = jnp.asarray(rng.randn(D, F) * 0.05, jnp.float32)
    wu = jnp.asarray(rng.randn(D, F) * 0.05, jnp.float32)
    wd = jnp.asarray(rng.randn(F, D) * 0.05, jnp.float32)

    y = torus.torus_ffn(x, wg, wu, wd, mesh)
    ref = (np.asarray(jax.nn.silu(x @ wg)) * np.asarray(x @ wu)) @ np.asarray(wd)
    print("torus FFN allclose:", np.allclose(np.asarray(y), ref, atol=1e-3))

    # show the collective schedule: neighbor permutes only
    f = shard_map(lambda xs, ws: torus.ring_allgather_matmul(xs, ws),
                  mesh=mesh, in_specs=(P("model", None), P(None, "model")),
                  out_specs=P(None, "model"))
    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((S, D), jnp.float32),
        jax.ShapeDtypeStruct((D, F), jnp.float32)).compile().as_text()
    counts = {k: len(re.findall(k, txt))
              for k in ("collective-permute", "all-gather", "all-reduce")}
    print("ring AG-matmul HLO collectives:", counts)
    srcdst = re.findall(r"source_target_pairs=\{([^}]*)\}", txt)
    if srcdst:
        print("first permute pairs (neighbor ring):", srcdst[0][:60], "...")


if __name__ == "__main__":
    main()
