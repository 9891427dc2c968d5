"""CGRA analytical simulator: paper claims C1-C4 hold in the model, plus
tile-mapper invariants and quantization/compression correctness."""
import jax.numpy as jnp
import numpy as np
from _prop import given, settings, st  # hypothesis, or deterministic fallback

from repro.core.cgra import (CGRAConfig, MXU_DIM, TPU_SCOPED_VMEM_BYTES,
                             select_block_shapes, simulate_gemm,
                             simulate_transformer_layer)
from repro.core.quant import compress_grad, dequantize, quantize


CFG = CGRAConfig()


def test_c4_blocking_increases_reuse_and_cuts_traffic():
    b = simulate_gemm(CFG, 256, 256, 256, "int8", blocked=True)
    n = simulate_gemm(CFG, 256, 256, 256, "int8", blocked=False)
    assert b.loads_words < n.loads_words / 2
    assert b.arithmetic_intensity > 4 * n.arithmetic_intensity
    assert b.macs == n.macs  # same math


def test_c2_mob_decoupling_cuts_stalls():
    dec = simulate_gemm(CFG, 256, 256, 256, "int8")
    ser = simulate_gemm(CGRAConfig(decoupled_mob=False), 256, 256, 256, "int8")
    assert dec.cycles < ser.cycles
    assert dec.stall_cycles < ser.stall_cycles


def test_c3_switchless_torus_saves_energy_and_latency():
    t, _ = simulate_transformer_layer(CFG, 256, 4, 64, 1024, seq=128)
    s, _ = simulate_transformer_layer(CGRAConfig(switched_noc=True),
                                      256, 4, 64, 1024, seq=128)
    assert s.energy_pj > t.energy_pj
    assert s.cycles >= t.cycles


def test_c1_pe_array_throughput_scales():
    small = simulate_gemm(CGRAConfig(pe_rows=2, pe_cols=2), 512, 512, 512, "int8")
    big = simulate_gemm(CGRAConfig(pe_rows=8, pe_cols=8), 512, 512, 512, "int8")
    assert big.compute_cycles * 15 < small.compute_cycles * 16


def test_ultra_low_power_class():
    """The edge config stays in the paper's ultra-low-power class (mW-scale,
    not watts) while sustaining useful GEMM throughput."""
    r = simulate_gemm(CFG, 128, 256, 128, "int8")
    assert r.power_mw < 10.0
    assert r.pe_utilization > 0.5


@settings(max_examples=20, deadline=None)
@given(m=st.integers(16, 2048), k=st.integers(16, 2048), n=st.integers(16, 2048))
def test_prop_tile_mapper_fits_vmem(m, k, n):
    bm, bk, bn = select_block_shapes(m, k, n, dtype_bytes=2, out_bytes=2)
    assert bm % MXU_DIM == bk % MXU_DIM == bn % MXU_DIM == 0
    assert 2 * (bm * bk + bk * bn) * 2 + bm * bn * 4 <= 8 * 1024 * 1024
    # the double-buffered bf16 output block and the f32 dot result count too
    assert (2 * (bm * bk + bk * bn) * 2 + 2 * bm * bn * (2 + 4)
            <= TPU_SCOPED_VMEM_BYTES // 2)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 512), k=st.integers(1, 512), n=st.integers(1, 512),
       blocked=st.booleans())
def test_prop_simulator_conservation(m, k, n, blocked):
    """MACs invariant; cycles >= compute bound; energy positive."""
    r = simulate_gemm(CFG, m, k, n, "int8", blocked=blocked)
    assert r.macs == m * n * k
    assert r.cycles >= r.compute_cycles
    assert r.energy_pj > 0
    assert 0 < r.pe_utilization <= 1.0


def test_grad_compression_error_feedback_converges():
    """Error feedback makes the *accumulated* compressed signal track the
    true gradient sum."""
    rng = np.random.RandomState(0)
    g_true = jnp.asarray(rng.randn(64, 64) * 1e-3, jnp.float32)
    err = jnp.zeros_like(g_true)
    total = jnp.zeros_like(g_true)
    for _ in range(50):
        qt, err = compress_grad(g_true, err)
        total = total + dequantize(qt)
    np.testing.assert_allclose(np.asarray(total) / 50, np.asarray(g_true),
                               atol=np.abs(g_true).max() * 0.02)


def test_quantize_axis_none_scalar_scale():
    x = jnp.asarray(np.random.RandomState(1).randn(10, 10), jnp.float32)
    qt = quantize(x, axis=None)
    assert qt.scale.shape == ()
    assert np.abs(np.asarray(dequantize(qt) - x)).max() <= float(
        jnp.abs(x).max()) / 127 + 1e-6
