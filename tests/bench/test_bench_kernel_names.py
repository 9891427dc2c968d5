"""The trace reduction's kernel lookup on HLO compiled for a described (not
attached) TPU v5e: ``bench/xplane.py`` ``kernel_of`` counts as a kernel's
time only its own custom call, never an instruction that reads the
kernel's output.  A kernel's name in the text of those readers (``name=``
on ``pallas_call`` puts it there, as ``fusion(%block_gemm.1)``) would be
counted while ``kernel_of`` matches names anywhere in the text, and every
roofline would read low; the program's kernels carry no ``name=`` until
the lookup matches the instruction's own name."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.xplane import kernel_of  # noqa: E402
from repro.kernels.block_gemm import block_gemm  # noqa: E402
from repro.kernels.decode_attention import flash_decode  # noqa: E402

# olmo-1b widths and a paged pool of 64-row pages
D, H, K, DH, B, NPP, PAGE = 2048, 16, 16, 128, 8, 16, 64
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def _compiled_hlo(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_no_consumer_of_a_kernel_reads_as_kernel_time(one_chip):
    pool = ((B * NPP + 1, K, PAGE, DH), BF16)
    texts = [
        _compiled_hlo(one_chip, lambda a, b: block_gemm(a, b) * 2,
                      ((256, D), BF16), ((D, D), BF16)),
        _compiled_hlo(
            one_chip,
            lambda q, k, v, pos, pages: flash_decode(
                q, k, v, pos, pages=pages) * 2,
            ((B, H, DH), BF16), pool, pool, ((B,), I32), ((B, NPP), I32)),
    ]
    for hlo in texts:
        lines = [x.strip() for x in hlo.splitlines()
                 if " = " in x and "tpu_custom_call" not in x]
        assert any("fusion(" in x for x in lines)
        assert [x for x in lines if kernel_of(x)] == []
