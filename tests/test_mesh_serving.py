"""Mesh-sharded serving vs single-device references.

The engine on a ``1xM`` model-parallel mesh must be *bit-identical* to the
single-device engine for greedy decode on dense configs (argmax is robust
to the float-reduction reorderings sharding introduces), and
logits-close (<= 1e-4) for the MoE expert-parallel path.  Multi-device
parity runs in a subprocess with
``--xla_force_host_platform_device_count=8`` so the main pytest process
keeps its single-device view (same isolation rule as test_torus.py);
mesh-spec parsing and device-count validation run in-process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp

from repro.serving import EngineConfig, MeshSpec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    assert jax.device_count() == 8, jax.device_count()

    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    from repro.serving import Engine, EngineConfig, MeshSpec

    cfg = reduce_config(get_config("cgra-edge"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = [[(7 * i + j) % cfg.vocab_size for j in range(5 + 3 * i)]
               for i in range(4)]
    kw = dict(max_batch=4, max_len=128, page_size=16)

    # dense greedy parity: bit-identical tokens across mesh widths and
    # prefill styles (whole-suffix and chunked)
    for chunk in (None, 8):
        base, _ = Engine(cfg, params,
                         EngineConfig(chunk_tokens=chunk, **kw)
                         ).generate(prompts, max_new=12)
        for m in (2, 8):
            eng = Engine(cfg, params,
                         EngineConfig(mesh=MeshSpec(1, m),
                                      chunk_tokens=chunk, **kw))
            out, _ = eng.generate(prompts, max_new=12)
            assert out == base, f"mesh 1x{m} chunk={chunk} diverged"
    print("DENSE-PARITY-OK")

    # radix prefix reuse under mesh: second batch shares the first's
    # prefix pages, decodes must stay identical to an engine without reuse
    shared = prompts[0] * 7          # 35 tokens: spans two full 16-row pages
    family = [shared + [t] for t in (1, 2, 3)]
    meng = Engine(cfg, params, EngineConfig(mesh=MeshSpec(1, 2), **kw))
    got, _ = meng.generate(family, max_new=8)
    assert meng.prefix_hit_rate > 0, "radix cache never hit under mesh"
    cold = Engine(cfg, params, EngineConfig(mesh=MeshSpec(1, 2),
                                            prefix_cache=False, **kw))
    want, _ = cold.generate(family, max_new=8)
    assert got == want, "prefix reuse changed tokens under mesh"
    print("RADIX-OK")

    # mid-stream chunked prefill: submit while decodes are in flight so
    # mixed steps interleave prefill chunks with decode under the mesh
    seng = Engine(cfg, params, EngineConfig(mesh=MeshSpec(1, 2),
                                            chunk_tokens=8, **kw))
    seng.submit(prompts[0], 16, 0.0, seed=0)
    results = seng.step()
    seng.submit(prompts[1], 16, 0.0, seed=1)   # joins mid-decode
    while seng.num_queued or seng.num_active:
        results.extend(seng.step())
    ref = Engine(cfg, params, EngineConfig(chunk_tokens=8, **kw))
    ref.submit(prompts[0], 16, 0.0, seed=0)
    rres = ref.step()
    ref.submit(prompts[1], 16, 0.0, seed=1)
    while ref.num_queued or ref.num_active:
        rres.extend(ref.step())
    tok = lambda rs: sorted((r.rid, tuple(r.generated)) for r in rs)
    assert tok(results) == tok(rres), "mid-stream prefill diverged"
    print("MIDSTREAM-OK")

    # resilience counters under mesh: the same composed scenario as
    # tests/test_resilience.py (one REJECTED / CANCELLED / FAULT /
    # DEADLINE each) must move every ServeStats counter exactly once
    # through the sharded executables
    from repro.serving import ChaosInjector, FinishReason
    chaos = ChaosInjector(schedule={"logits.nan": {2}, "clock.skew": {6}},
                          skew_s=1000.0)
    reng = Engine(cfg, params,
                  EngineConfig(mesh=MeshSpec(1, 2), max_batch=1, max_len=128,
                               page_size=16, decode_chunk=4, max_queue=2,
                               prefix_cache=False),
                  chaos=chaos)
    mk = lambda i: [(11 * i + j) % cfg.vocab_size for j in range(20)]
    ra = reng.submit(mk(1), 6)                      # FAULT at tick 2
    rb = reng.submit(mk(2), 6)                      # cancelled in queue
    rc = reng.submit(mk(3), 6)                      # queue full: REJECTED
    assert reng.cancel(rb)
    rd = reng.submit(mk(4), 30, deadline_s=5.0)     # expires at tick 6
    rres = []
    while reng.num_queued or reng.num_active:
        rres.extend(reng.step())
    rres.extend(reng.run())
    rmap = {r.rid: r.finish_reason for r in rres}
    assert rmap == {ra: FinishReason.FAULT, rb: FinishReason.CANCELLED,
                    rc: FinishReason.REJECTED, rd: FinishReason.DEADLINE}, rmap
    s = reng.stats
    assert (s.rejected, s.cancelled, s.faults_isolated, s.deadline_expired,
            s.preempted) == (1, 1, 1, 1, 0)
    assert reng.pool.num_free == reng.pool.n_pages - 1
    print("RESILIENCE-OK")

    # MoE expert-parallel decode: tokens match greedy single-device and
    # prefill logits stay within 1e-4
    mcfg = reduce_config(get_config("qwen3-moe-30b-a3b"))
    mparams = M.init(mcfg, jax.random.PRNGKey(1))
    mp = [[(3 * i + j) % mcfg.vocab_size for j in range(6 + 2 * i)]
          for i in range(3)]
    mref, _ = Engine(mcfg, mparams, EngineConfig(**kw)).generate(mp, max_new=8)
    meng = Engine(mcfg, mparams, EngineConfig(mesh=MeshSpec(1, 2), **kw))
    assert meng.cfg.moe_shard_map, "expert-parallel routing not enabled"
    mout, _ = meng.generate(mp, max_new=8)
    assert mout == mref, "MoE greedy tokens diverged under mesh"

    from repro.launch.sharding import activation_mesh
    toks = jnp.asarray(np.array([mp[0]]), jnp.int32)
    lg_ref = M.prefill(mcfg, mparams, {"tokens": toks})[0]
    mesh = MeshSpec(1, 2).build()
    scfg = mcfg.with_(moe_shard_map=True)
    sp = M.shard_params(scfg, mparams, mesh)
    with activation_mesh(mesh):
        lg = jax.jit(lambda p, t: M.prefill(scfg, p, {"tokens": t})[0])(
            sp, toks)
    d = float(jnp.max(jnp.abs(lg - lg_ref)))
    assert d <= 1e-4, f"MoE prefill logits diverged: {d}"
    print("MOE-PARITY-OK")
""")


@pytest.mark.slow
def test_mesh_serving_parity_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    out = res.stdout
    for sentinel in ("DENSE-PARITY-OK", "RADIX-OK", "MIDSTREAM-OK",
                     "RESILIENCE-OK", "MOE-PARITY-OK"):
        assert sentinel in out, out + res.stderr


# -- in-process: spec parsing and mesh construction -------------------------

def test_mesh_spec_parse():
    assert MeshSpec.parse("1x8") == MeshSpec(1, 8)
    assert MeshSpec.parse("2x4") == MeshSpec(2, 4)
    assert MeshSpec.parse("4") == MeshSpec(1, 4)        # bare model width
    assert MeshSpec.parse("2×4") == MeshSpec(2, 4)      # unicode multiply
    assert MeshSpec.parse(MeshSpec(1, 2)) == MeshSpec(1, 2)
    assert MeshSpec(2, 4).size == 8
    with pytest.raises(ValueError):
        MeshSpec.parse("1x2x3")
    with pytest.raises(ValueError):
        MeshSpec.parse("ax2")
    with pytest.raises(ValueError):
        MeshSpec(0, 4)


def test_engine_config_coerces_mesh_strings():
    ec = EngineConfig(mesh="1x2")
    assert ec.mesh == MeshSpec(1, 2)
    assert EngineConfig(mesh=None).mesh is None
    assert EngineConfig(mesh=MeshSpec(1, 4)).mesh == MeshSpec(1, 4)


def test_make_device_mesh_validates_count():
    from repro.launch.mesh import make_device_mesh
    n = jax.device_count()
    mesh = make_device_mesh((1, n), ("data", "model"))
    assert dict(mesh.shape) == {"data": 1, "model": n}
    with pytest.raises(ValueError, match="devices"):
        make_device_mesh((1, n + 1), ("data", "model"))


def test_make_production_mesh_validates_count():
    from repro.launch.mesh import make_production_mesh
    n = jax.device_count()
    mesh = make_production_mesh(shape=(1, n))
    assert mesh.devices.size == n
    with pytest.raises(ValueError, match="device"):
        make_production_mesh(shape=(3, n * 5))


def test_mesh_spec_build_single_device_ok():
    # a 1x1 spec builds on any host — the degenerate mesh used by tests
    mesh = MeshSpec(1, 1).build()
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_psum_bytes_counts_scan_trips_and_the_larger_branch():
    """Per-device result bytes of every ``psum`` in the manual regions:
    a scan of 3 around one f32 [8, 16] sum, then a ``cond`` whose larger
    branch sums twice."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import psum_bytes, tp_shard_map

    mesh = MeshSpec(1, 1).build()
    row = tp_shard_map(lambda x, w: lax.psum(x @ w, "model"), mesh,
                       (P(None, "model"), P("model", None)), P())

    def step(x, w, flag):
        def body(c, _):
            return c + row(x, w), None
        c, _ = lax.scan(body, jnp.zeros((8, 16)), None, length=3)
        return lax.cond(flag, lambda c: c + row(x, w) + row(x, w),
                        lambda c: c + row(x, w), c)

    x, w = jnp.ones((8, 32)), jnp.ones((32, 16))
    traced = jax.jit(step).trace(x, w, True)
    assert psum_bytes(traced.jaxpr) == (3 + 2) * 8 * 16 * 4
