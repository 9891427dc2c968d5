"""Compile guards: the serving path's Pallas kernels at olmo-1b widths,
lowered and compiled for a described (not attached) TPU v5e by the TPU
compiler installed beside JAX.

Interpret-mode parity cannot show that Mosaic accepts a kernel's block
shapes or its VMEM use; these compiles can, in about a second each, with no
chip.  The topology is described inside a fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_gemm import block_gemm, block_gemm_int8
from repro.kernels.decode_attention import fd_paged_spec, flash_decode
from repro.kernels.flash_attention import flash_attention

# olmo-1b (configs/olmo_1b.py) and the engine's paged pool around it
D, H, K, DH, FF = 2048, 16, 16, 128, 8192
B, MAX_LEN = 8, 1024
# EngineConfig allows any multiple of 8 rows per page; 64 is its default
PAGES = [8, 16, 64]
BF16, I32, F32, I8 = jnp.bfloat16, jnp.int32, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(page):
    """Head-major page pool of a full batch, plus the trash page, and the
    pages per sequence."""
    npp = MAX_LEN // page
    return ((B * npp + 1, K, page, DH), BF16), npp


@pytest.mark.parametrize("page", PAGES)
def test_flash_decode_paged(one_chip, page):
    """One grid step per (slot, page) streams the page of all K heads."""
    pool, npp = _pool(page)
    spec = fd_paged_spec(B, H, K, DH, DH, page, npp, pool[0][0])
    assert spec.grid == (B, 1, npp)
    assert ([op.block_shape for op in spec.inputs[1:]]
            == [(None, 1, K, page, DH)] * 2)
    hlo = _compiled_hlo(
        one_chip,
        lambda q, k, v, pos, pages: flash_decode(q, k, v, pos, pages=pages),
        ((B, H, DH), BF16), pool, pool, ((B,), I32), ((B, npp), I32))
    assert "tpu_custom_call" in hlo


def test_flash_decode_paged_mla(one_chip):
    """MLA's decode (minicpm3-4b widths, 64-row pages): one fused
    ``[latent | k_rope]`` pool of one latent head passed as both k and v,
    the value read narrowed to the latent's 256 columns by the BlockSpec."""
    kvr, dr, Hm, npp = 256, 32, 40, MAX_LEN // 64
    pool = ((B * npp + 1, 1, 64, kvr + dr), BF16)
    hlo = _compiled_hlo(
        one_chip,
        lambda q, kv, pos, pages: flash_decode(q, kv, kv, pos, pages=pages,
                                               dv=kvr, scale=96 ** -0.5),
        ((B, Hm, kvr + dr), BF16), pool, ((B,), I32), ((B, npp), I32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_dense(one_chip):
    hlo = _compiled_hlo(one_chip, flash_attention, ((1, H, 512, DH), BF16),
                        ((1, K, 512, DH), BF16), ((1, K, 512, DH), BF16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("page", PAGES)
def test_flash_attention_paged_past(one_chip, page):
    C = 256  # one chunked-prefill chunk over the paged past
    pool, npp = _pool(page)
    hlo = _compiled_hlo(
        one_chip,
        lambda q, k, v, pages, qs, kl: flash_attention(
            q, k, v, pages=pages, q_start=qs, k_len=kl),
        ((1, H, C, DH), BF16), pool, pool, ((1, npp), I32), ((1,), I32),
        ((1,), I32))
    assert "tpu_custom_call" in hlo


# a decode batch, a long prefill chunk, a whole 2048-token prefill
@pytest.mark.parametrize("M", [8, 512, 2048])
def test_block_gemm(one_chip, M):
    hlo = _compiled_hlo(one_chip, block_gemm, ((M, D), BF16), ((D, FF), BF16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("M", [512, 2048])  # 512 once overran scoped VMEM
def test_block_gemm_int8(one_chip, M):
    hlo = _compiled_hlo(one_chip, block_gemm_int8, ((M, D), I8),
                        ((D, FF), I8), ((M, 1), F32), ((1, FF), F32))
    assert "tpu_custom_call" in hlo


# deepseek-67b-tp4 (bench/configs/deepseek-67b-tp4.json): one chip's share
# of a layer of 8192 under 4-way tensor parallelism, 16 of 64 query heads
# over 2 of 8 KV heads (G = 8), 5504 of 22016 FFN columns, 25600 of 102400
# vocabulary rows; a full decode batch of 32 and a 256-row prompt chunk
# beside it (288 rows), over a pool of 1025 64-row pages
TP_K, TP_H, TP_PAGES, TP_B, TP_NPP = 2, 16, 1025, 32, 32
TP_GEMMS = [(8192, 2048), (8192, 256), (8192, 5504), (5504, 8192),
            (2048, 8192), (8192, 25600)]


@pytest.mark.parametrize("M", [32, 288])
@pytest.mark.parametrize("KN", TP_GEMMS, ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_block_gemm_tp4_shard(one_chip, M, KN):
    """q, k/v, gate/up, down, o and the head (f32 logits) of one shard."""
    Kd, N = KN
    out = F32 if N == 25600 else None
    hlo = _compiled_hlo(one_chip,
                        lambda a, b: block_gemm(a, b, out_dtype=out),
                        ((M, Kd), BF16), ((Kd, N), BF16))
    assert "tpu_custom_call" in hlo


def test_flash_decode_paged_tp4_shard(one_chip):
    pool = ((TP_PAGES, TP_K, 64, DH), BF16)
    hlo = _compiled_hlo(
        one_chip,
        lambda q, k, v, pos, pages: flash_decode(q, k, v, pos, pages=pages),
        ((TP_B, TP_H, DH), BF16), pool, pool, ((TP_B,), I32),
        ((TP_B, TP_NPP), I32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_paged_tp4_shard(one_chip):
    pool = ((TP_PAGES, TP_K, 64, DH), BF16)
    hlo = _compiled_hlo(
        one_chip,
        lambda q, k, v, pages, qs, kl: flash_attention(
            q, k, v, pages=pages, q_start=qs, k_len=kl),
        ((1, TP_H, 256, DH), BF16), pool, pool, ((1, TP_NPP), I32),
        ((1,), I32), ((1,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("chips", [1, 4])
def test_paged_steps_keep_pools_in_place(topo, chips):
    """The decode chunk and the mixed step as the chip runs them (Pallas
    kernels, bf16, 64-row pages of 128-wide heads; 2 layers of 2048 and a
    small vocabulary), on one chip and on a 1x4 mesh: the stacked KV pools
    are written in place and read by the kernels in the layout they are
    stored in, with no whole-pool copy, slice or transpose
    (``tests/test_paged_pool_inplace.py`` has the scan).  A row-sized
    update once made the compiler store the stack head-minor and copy it
    to and from the kernels' layout every layer."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs.base import ArchConfig
    from repro.launch.mesh import make_device_mesh
    from repro.launch.sharding import tree_pspecs
    from repro.models import model as M
    from repro.models.params import shape_tree
    from repro.serving import EngineConfig
    from repro.serving.engine import ModelRunner
    from test_paged_pool_inplace import pool_ops

    cfg = ArchConfig(name="olmo-2l", family="dense", num_layers=2,
                     d_model=D, num_heads=H, num_kv_heads=K, head_dim=DH,
                     d_ff=512, vocab_size=512,
                     norm_type="layernorm_nonparam", param_dtype=BF16,
                     compute_dtype=BF16, kernel_mode="pallas")
    Bs, npp, page, C = 4, 4, 64, 128
    n_pages = Bs * npp + 1
    r = ModelRunner(cfg, None, EngineConfig(
        max_batch=Bs, max_len=npp * page, page_size=page, n_pages=n_pages,
        chunk_tokens=C, decode_chunk=2, kernel_mode="pallas"))
    rep = SingleDeviceSharding(topo.devices[0])
    if chips > 1:
        # the runner's steps take its mesh when they are wrapped for jit
        r.mesh = make_device_mesh((1, chips), ("data", "model"),
                                  devices=topo.devices)
        r.tp = chips
        r.decode_fn = jax.jit(r._traced(r._decode_chunk), donate_argnums=(1,))
        rep = NamedSharding(r.mesh, PartitionSpec())

    def sds(specs, dtype):
        shapes = shape_tree(specs, dtype)
        where = (tree_pspecs(specs, r.mesh) if chips > 1
                 else jax.tree.map(lambda _: rep, shapes))
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, where)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    state = (sds(M.param_specs(cfg), BF16), sds(r.cache_specs, BF16))
    dec = (arg((Bs, npp), I32), arg((Bs,), I32), arg((Bs,), I32),
           arg((Bs,), I32), arg((Bs,), F32), arg((Bs, 2), jnp.uint32),
           arg((Bs,), jnp.bool_))
    mixed = (arg((1, C), I32), arg((1, npp), I32), arg((), I32),
             arg((), I32), arg((), F32), arg((2,), jnp.uint32),
             arg((), jnp.bool_), *dec)
    for fn, args in ((r.decode_fn, dec), (r.mixed_fn(C, 1), mixed)):
        hlo = fn.lower(*state, *args).compile().as_text()
        assert "tpu_custom_call" in hlo
        assert pool_ops(hlo, (n_pages, K, page, DH), layers=2,
                        tp=chips) == []
