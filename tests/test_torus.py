"""Torus ring-collective schedules vs dense references.

Multi-device tests run in a subprocess with
``--xla_force_host_platform_device_count=8`` so the main pytest process keeps
its single-device view (per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core import torus
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("model",))
    rng = np.random.RandomState(0)
    T, D, F = 64, 32, 48
    x = rng.randn(T, D).astype(np.float32)
    w = rng.randn(D, F).astype(np.float32)

    f = shard_map(lambda xs, ws: torus.ring_allgather_matmul(xs, ws),
                  mesh=mesh, in_specs=(P("model", None), P(None, "model")),
                  out_specs=P(None, "model"))
    assert np.allclose(np.asarray(f(x, w)), x @ w, atol=1e-4), "AG-matmul"

    w2 = rng.randn(F, D).astype(np.float32)
    h = rng.randn(T, F).astype(np.float32)
    g = shard_map(lambda hs, ws: torus.matmul_reducescatter_ring(hs, ws),
                  mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
                  out_specs=P("model", None))
    assert np.allclose(np.asarray(g(h, w2)), h @ w2, atol=1e-3), "MM-RS"

    vs = np.stack([rng.randn(33).astype(np.float32) for _ in range(8)])
    rr = shard_map(lambda a: torus.ring_allreduce(a[0])[None], mesh=mesh,
                   in_specs=(P("model", None),), out_specs=P("model", None))(vs)
    assert np.allclose(np.asarray(rr)[0], vs.sum(0), atol=1e-4), "ring-AR"

    B, S, D2, F2 = 2, 16, 32, 64
    x3 = rng.randn(B, S, D2).astype(np.float32)
    wg = rng.randn(D2, F2).astype(np.float32)
    wu = rng.randn(D2, F2).astype(np.float32)
    wd = rng.randn(F2, D2).astype(np.float32)
    yt = torus.torus_ffn(jnp.asarray(x3), wg, wu, wd, mesh)
    ref = (np.asarray(jax.nn.silu(x3 @ wg)) * (x3 @ wu)) @ wd
    assert np.allclose(np.asarray(yt), ref, atol=1e-3), "torus-FFN"

    # HLO check: ring schedules lower to collective-permute only (C3)
    xs = jax.ShapeDtypeStruct((T, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((D, F), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    assert "collective-permute" in txt, "expected neighbor permutes"
    assert "all-gather" not in txt, "ring schedule must not all-gather"

    # int8-compressed cross-pod gradient mean (training/compress.py)
    from repro.training.compress import compressed_mean
    g8 = shard_map(lambda a: compressed_mean(a[0], "model")[0][None],
                   mesh=mesh, in_specs=(P("model", None),),
                   out_specs=P("model", None))
    vals = np.stack([np.full((257,), i, np.float32) for i in range(8)])
    got = np.asarray(g8(vals))[0]
    assert np.allclose(got, vals.mean(0), atol=vals.max() / 100), "compressed mean"
    print("TORUS-OK")
""")


@pytest.mark.slow
def test_torus_collectives_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "TORUS-OK" in res.stdout, res.stdout + res.stderr
