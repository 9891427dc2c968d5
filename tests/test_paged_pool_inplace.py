"""The paged KV pools stay one stacked buffer ``[L, P, K, ps, d]`` through
a compiled decode chunk and mixed step: each layer writes its new rows into
the stack in place and the attention kernels read it at the layer's index,
so no layer's pool is sliced out, stacked back or copied.

A tiny paged ``ModelRunner`` (4 layers, float32) is compiled on the CPU,
without a mesh and on a 1x4 mesh of host devices (in a subprocess: the
host device count is fixed when JAX starts), and its HLO is scanned for

- a ``copy``, ``dynamic-slice`` or ``transpose`` whose result has the
  stack's or one layer's pool shape (as a device holds it), and
- a ``dynamic-update-slice`` whose update has one layer's pool shape.

In interpret mode the scan leaves out copies: Pallas' interpreter runs a
kernel as a loop that carries and rewrites every operand, so it copies the
pools it is handed by design.  The chip's own program is compiled for a
described TPU v5e in ``tests/test_tpu_compile.py``, copies included."""
import functools
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: layers, pages, page rows, decode slots, chunk rows, max rows a sequence
L, P, PS, B, C, MAX_LEN = 4, 13, 16, 3, 16, 64

#: an HLO instruction: name, result dims, opcode, operands and attributes
_INST = re.compile(
    r"^\s*(?:ROOT )?(%\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$")


def pool_ops(hlo: str, pool: tuple, layers: int = L, tp: int = 1,
             copies: bool = True) -> list[str]:
    """Instructions of ``hlo`` that move a whole pool: ``pool`` is one
    layer's ``(P, K, ps, d)``, stacked ``layers`` deep, ``tp`` the ways its
    heads are split over devices."""
    def key(dims):
        return tuple(sorted(d for d in dims if d != 1))

    P_, K, ps, d = pool
    layer = {key((P_, K // t, ps, d)) for t in {1, tp}}
    whole = {key((layers, P_, K // t, ps, d)) for t in {1, tp}}
    moves = {"dynamic-slice", "transpose"} | ({"copy"} if copies else set())
    shapes, found = {}, []
    for line in hlo.splitlines():
        m = _INST.match(line)
        if not m:
            continue
        name, dims, op, rest = m.groups()
        shapes[name] = key(int(x) for x in dims.split(",") if x)
        if op in moves and shapes[name] in layer | whole:
            found.append(line.strip())
        if op == "dynamic-update-slice":
            update = rest.split(", ")[1].split(")")[0]
            if shapes.get(update) in layer:
                found.append(line.strip())
    return found


def compiled(mode: str, mesh=None) -> dict:
    """One layer's pool shape, the tensor-parallel ways and the compiled
    HLO of the decode chunk and of the mixed step of a tiny paged runner."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    from repro.serving import EngineConfig
    from repro.serving.engine import ModelRunner

    cfg = dataclasses.replace(reduce_config(get_config("olmo-1b")),
                              num_layers=L, kernel_mode=mode)
    r = ModelRunner(cfg, M.init(cfg, jax.random.PRNGKey(0)),
                    EngineConfig(max_batch=B, max_len=MAX_LEN, page_size=PS,
                                 n_pages=P, decode_chunk=2, kernel_mode=mode,
                                 mesh=mesh))
    npp = MAX_LEN // PS

    def z(*shape, dt=np.int32):
        return np.zeros(shape, dt)

    dec = (z(B, npp), z(B), z(B), z(B), z(B, dt=np.float32),
           z(B, 2, dt=np.uint32), z(B, dt=bool))
    mixed = (z(1, C), z(1, npp), np.int32(3), np.int32(5), np.float32(0),
             z(2, dt=np.uint32), np.bool_(False), *dec)
    fns = {"decode": r.decode_fn.lower(r.params, r.caches, *dec),
           "mixed": r.mixed_fn(C, 1).lower(r.params, r.caches, *mixed)}
    return {"pool": (P, cfg.num_kv_heads, PS, cfg.head_dim), "tp": r.tp,
            "hlo": {k: f.compile().as_text() for k, f in fns.items()}}


MESH = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, sys.argv[1])
    from test_paged_pool_inplace import compiled
    from repro.serving import MeshSpec
    print("RESULT " + json.dumps({m: compiled(m, MeshSpec(1, 4))
                                  for m in ("reference", "interpret")}))
""")


@pytest.fixture(scope="module")
def on_mesh():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", MESH,
                          os.path.dirname(__file__)], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [x for x in res.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, res.stdout[-3000:] + res.stderr[-3000:]
    return json.loads(line[0][len("RESULT "):])


@functools.cache
def one_device(mode: str) -> dict:
    return compiled(mode)


@pytest.mark.parametrize("step", ["decode", "mixed"])
@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_pools_stay_in_place(mode, step):
    got = one_device(mode)
    assert pool_ops(got["hlo"][step], got["pool"],
                    copies=mode == "reference") == []


@pytest.mark.parametrize("step", ["decode", "mixed"])
@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_pools_stay_in_place_on_mesh(on_mesh, mode, step):
    got = on_mesh[mode]
    assert got["tp"] == 4
    assert pool_ops(got["hlo"][step], tuple(got["pool"]), tp=4,
                    copies=mode == "reference") == []
