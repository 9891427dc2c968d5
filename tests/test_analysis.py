"""Mutation harness for ``repro.analysis``: every rule must *fire* on a
seeded defect (no dead rules) and stay silent on the healthy equivalent,
plus regression tests pinning the genuine findings the checker surfaced
(f32 logits contract, clamped paged index maps)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (
    RULES,
    Report,
    check_donation,
    check_kernel_spec,
    check_logits_dtype,
    lint_hlo,
    lint_jaxpr,
    param_gather_shapes,
)
from repro.analysis.bounds import _GuardedTable
from repro.analysis.findings import Finding
from repro.kernels.spec import KernelSpec, OperandSpec, ScalarSpec
from repro.models import model as M
from repro.serving.paging import PagePool, RadixCache, check_invariants


def rules_of(findings):
    return {f.rule for f in findings}


def lint_of(fn, *args):
    return lint_jaxpr(jax.make_jaxpr(fn)(*args))


# ---------------------------------------------------------------------------
# J rules: jaxpr lints
# ---------------------------------------------------------------------------

def test_j001_fires_on_stray_int8_dequant():
    def bad(x):
        return x.astype(jnp.float32) * 2.0

    fs = lint_of(bad, jnp.zeros((4, 4), jnp.int8))
    assert rules_of(fs) == {"J001"}
    assert fs[0].file and "test_analysis" in fs[0].file  # provenance


def test_j001_allows_int8_to_int32():
    def ok(x):
        return x.astype(jnp.int32) + 1

    assert lint_of(ok, jnp.zeros((4, 4), jnp.int8)) == []


def test_j002_fires_on_unaccumulated_bf16_dot():
    def bad(a, b):
        return a @ b

    a = jnp.zeros((8, 8), jnp.bfloat16)
    assert "J002" in rules_of(lint_of(bad, a, a))


def test_j002_fires_on_int8_dot_without_int32():
    def bad(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    a = jnp.zeros((8, 8), jnp.int8)
    assert "J002" in rules_of(lint_of(bad, a, a))


def test_j002_silent_on_f32_accumulated_dot():
    def ok(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.bfloat16)

    a = jnp.zeros((8, 8), jnp.bfloat16)
    assert lint_of(ok, a, a) == []


def test_j003_fires_on_host_callback():
    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    assert "J003" in rules_of(lint_of(bad, jnp.zeros(4)))


def test_j004_fires_on_large_baked_constant():
    big = jnp.asarray(np.ones((256, 256), np.float32))  # 256 KiB

    def bad(x):
        return x + big

    fs = lint_of(bad, jnp.zeros((256, 256), jnp.float32))
    assert "J004" in rules_of(fs)


def test_j005_fires_on_f64_leak():
    with jax.enable_x64():
        def bad(x):
            return x.astype(jnp.float64) * 2.0

        fs = lint_of(bad, jnp.zeros(4, jnp.float32))
    assert "J005" in rules_of(fs)


def test_j006_fires_on_bf16_logits():
    aval = jax.ShapeDtypeStruct((2, 1, 256), jnp.bfloat16)
    assert rules_of(check_logits_dtype(aval)) == {"J006"}
    ok = jax.ShapeDtypeStruct((2, 1, 256), jnp.float32)
    assert check_logits_dtype(ok) == []


# ---------------------------------------------------------------------------
# J007: compiled-HLO sharded-surface lint (pure text — no mesh needed)
# ---------------------------------------------------------------------------

_HLO_PARAM_GATHER = """
  %ag.1 = f32[128,16384]{1,0} all-gather(f32[128,2048]{1,0} %w), dimensions={1}
"""
_HLO_ACT_GATHER = """
  %ag.2 = f32[2,8,64]{2,1,0} all-gather(f32[2,1,64]{2,1,0} %x), dimensions={1}
"""
_HLO_HOST = """
  %cc = f32[4]{0} custom-call(f32[4]{0} %y), custom_call_target="SendToHost"
  %of = token[] outfeed(f32[4]{0} %z, token[] %tok)
"""


def test_j007_fires_on_full_param_all_gather():
    fs = lint_hlo(_HLO_PARAM_GATHER, {(128, 16384)})
    assert rules_of(fs) == {"J007"}
    assert "(128, 16384)" in fs[0].message


def test_j007_ignores_activation_all_gather():
    # the gathered shape matches no parameter leaf -> legitimate
    # activation collective, not a finding
    assert lint_hlo(_HLO_ACT_GATHER, {(128, 16384)}) == []


def test_j007_fires_on_host_transfers():
    fs = lint_hlo(_HLO_HOST, set())
    assert len(fs) == 2 and rules_of(fs) == {"J007"}
    msgs = " ".join(f.message for f in fs)
    assert "SendToHost" in msgs and "outfeed" in msgs


def test_j007_silent_on_clean_module():
    clean = """
  %dot = f32[64,64]{1,0} dot(f32[64,32]{1,0} %a, f32[32,64]{1,0} %b)
  %ar = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %dot), to_apply=%sum
  %cc = f32[4]{0} custom-call(f32[4]{0} %y), \
custom_call_target="annotate_device_placement"
"""
    assert lint_hlo(clean, {(64, 64), (128, 16384)}) == []


def test_j007_dedupes_repeated_gathers():
    fs = lint_hlo(_HLO_PARAM_GATHER * 3, {(128, 16384)})
    assert len(fs) == 1


def test_param_gather_shapes_layer_slices():
    params = {"stacked": np.zeros((4, 128, 256), np.float32),
              "flat": np.zeros((256, 512), np.float32),
              "tiny": np.zeros((8,), np.float32)}
    shapes = param_gather_shapes(params)
    assert (4, 128, 256) in shapes        # full stacked leaf
    assert (128, 256) in shapes           # per-layer slice
    assert (256, 512) in shapes           # plain 2D leaf
    assert (8,) not in shapes             # below the size threshold


def test_j007_fires_on_real_sharded_mutation():
    """End-to-end on this host's devices: shard a weight over a 2-device
    mesh, then undo the placement with a replicate constraint — the SPMD
    partitioner must emit a full-parameter all-gather that J007 catches."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (multi-device CI lane)")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    w = jax.device_put(jnp.zeros((128, 128), jnp.float32),
                       NamedSharding(mesh, P("model", None)))
    bad = jax.jit(lambda w: jax.lax.with_sharding_constraint(
        w, NamedSharding(mesh, P())) * 2.0)
    hlo = bad.lower(w).compile().as_text()
    fs = lint_hlo(hlo, param_gather_shapes({"w": w}))
    assert rules_of(fs) == {"J007"}


# ---------------------------------------------------------------------------
# D rules: donation
# ---------------------------------------------------------------------------

def test_d001_fires_on_dead_donation():
    def fn(a, b):
        return a + 0.0  # b's buffer matches no output

    args = (jnp.zeros((4,), jnp.float32), jnp.zeros((8,), jnp.float32))
    fs = check_donation(fn, args, (1,))
    assert rules_of(fs) == {"D001"}


def test_d002_fires_on_duplicate_donation():
    def fn(a, b):
        return a + b  # one output cannot absorb two donated buffers

    args = (jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.float32))
    fs = check_donation(fn, args, (0, 1))
    assert rules_of(fs) == {"D002"}


def test_donation_silent_on_absorbed_buffers():
    def fn(a, b):
        return a + b, b * 2.0

    args = (jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.float32))
    assert check_donation(fn, args, (0, 1)) == []


# ---------------------------------------------------------------------------
# K rules: BlockSpec bounds proofs
# ---------------------------------------------------------------------------

PAGES = ScalarSpec("pages", (2, 4), 0, 8)
POS = ScalarSpec("pos", (2,), 0, 32)


def test_k001_fires_on_unclamped_table_read():
    # pos reaches 32 (frozen slot) -> pos // 8 == 4 == table width: OOB
    spec = KernelSpec(
        "mut", (2, 4), scalars=(POS, PAGES),
        operands=(OperandSpec(
            "kv", (8, 128), lambda b, ik, pos_ref, pages_ref:
            (pages_ref[b, pos_ref[b] // 8], ik), (9, 4)),))
    fs = check_kernel_spec(spec)
    assert rules_of(fs) == {"K001"}
    assert any("out of bounds" in f.message for f in fs)


def test_k001_fires_on_oob_block_index():
    spec = KernelSpec(
        "mut", (2, 4), scalars=(),
        operands=(OperandSpec("kv", (8, 128), lambda b, ik: (b, ik + 1),
                              (2, 4)),))
    assert rules_of(check_kernel_spec(spec)) == {"K001"}


def test_k002_fires_on_masked_but_not_remapped_blocks():
    # identity map + gating predicate: dead blocks still DMA
    spec = KernelSpec(
        "mut", (2, 4), scalars=(POS,),
        operands=(OperandSpec("kv", (8, 128), lambda b, ik, pos: (b, ik),
                              (2, 4)),),
        block_live=lambda b, ik, pos: ik * 8 <= pos[b])
    assert rules_of(check_kernel_spec(spec)) == {"K002"}


def test_k003_fires_on_output_varying_along_reduction():
    spec = KernelSpec(
        "mut", (2, 4), scalars=(),
        operands=(OperandSpec("o", (8, 128), lambda i, k: (i, k), (2, 4),
                              is_output=True),),
        reduction_axes=(1,))
    assert rules_of(check_kernel_spec(spec)) == {"K003"}


def test_guarded_table_records_negative_indices():
    oob = []
    t = _GuardedTable("t", np.arange(8), oob)
    assert t[np.array([-1, 3])][1] == 3  # clipped, evaluation continues
    assert oob and "out of bounds" in oob[0]


def test_shipped_kernel_specs_prove_clean():
    from repro.kernels.block_gemm import gemm_spec
    from repro.kernels.decode_attention import fd_paged_spec
    from repro.kernels.flash_attention import fa_dense_spec, fa_paged_spec

    for spec in (fa_dense_spec(2, 4, 2, 96, 96, 64),
                 fa_paged_spec(2, 4, 2, 32, 64, 16, 4, 9),
                 fd_paged_spec(2, 4, 2, 64, 64, 16, 4, 9),
                 gemm_spec(64, 128, 256),
                 gemm_spec(64, 128, 256, int8=True)):
        assert check_kernel_spec(spec) == [], spec.name


def test_paged_kv_map_oob_without_clamp():
    """Regression: the paged decode kv map *must* clamp — a frozen slot
    (pos == capacity) would otherwise read past the page table."""
    from repro.kernels.decode_attention import fd_paged_spec

    spec = fd_paged_spec(2, 4, 2, 64, 64, 16, 4, 9)
    assert any(op.name == "k" for op in spec.operands)

    def unclamped(b, kh, ik, pos_ref, start_ref, pages_ref, layer_ref):
        return (layer_ref[0], pages_ref[b, ik], kh, 0, 0)  # no [lo, hi] clamp

    mutated = dataclasses.replace(spec, operands=tuple(
        dataclasses.replace(op, index_map=unclamped)
        if op.name in ("k", "v") else op
        for op in spec.operands))
    assert "K002" in rules_of(check_kernel_spec(mutated))


def _seed_seq_major(spec, kv_map):
    """The paged spec as it shipped with a sequence-major pool
    ``[L, n_pages, ps, K, d]``: k/v blocks ``(-, 1, ps, 1, d)`` — one head
    picked by a second-to-last block dim of 1 over K."""
    def mutate(op):
        if op.name not in ("k", "v"):
            return op
        _, _, kb, ps, d = op.block_shape
        layers, n_pages = op.grid_blocks[0], op.grid_blocks[1]
        K = op.grid_blocks[2] * kb
        return dataclasses.replace(op, block_shape=(None, 1, ps, 1, d),
                                   grid_blocks=(layers, n_pages, 1, K, 1),
                                   index_map=kv_map)
    return dataclasses.replace(spec, operands=tuple(map(mutate,
                                                        spec.operands)))


@pytest.mark.parametrize("kernel", ["flash_decode", "flash_attention"])
def test_k004_fires_on_seq_major_paged_blocks(kernel):
    """Mutation: the sequence-major paged layout Mosaic refused to lower
    for MHA/GQA (K > 1) — the checker must catch it on the CPU."""
    from repro.kernels.decode_attention import fd_paged_spec
    from repro.kernels.flash_attention import fa_paged_spec

    if kernel == "flash_decode":
        spec = _seed_seq_major(
            fd_paged_spec(2, 4, 2, 128, 128, 16, 4, 9),
            lambda b, kh, ik, pos, start, pages, layer: (
                layer[0], pages[b, 0], 0, kh, 0))
    else:
        spec = _seed_seq_major(
            fa_paged_spec(2, 4, 2, 32, 128, 16, 4, 9),
            lambda b, h, iq, ik, qs, kl, pages, layer: (
                layer[0], pages[b, 0], 0, h // 2, 0))
    fs = [f for f in check_kernel_spec(spec) if f.rule == "K004"]
    assert {f.message.split("'")[1] for f in fs} == {"k", "v"}


def test_k004_fires_on_unaligned_gemm_block_and_accepts_full_dims():
    from repro.kernels.block_gemm import gemm_spec

    assert "K004" in rules_of(check_kernel_spec(
        gemm_spec(256, 256, 256, block_shape=(100, 128, 128))))
    # a ragged block equal to the whole (padded) dim is legal
    full = KernelSpec("full", (1,), scalars=(), operands=(OperandSpec(
        "x", (5, 24), lambda i: (0, 0), (1, 1)),))
    assert check_kernel_spec(full) == []


# ---------------------------------------------------------------------------
# P001: paging invariants
# ---------------------------------------------------------------------------

def test_p001_fires_on_corrupted_refcount():
    pool = PagePool(8)
    pool.alloc()
    pool._rc[2] = 5  # phantom references
    bad = check_invariants(pool)
    assert bad and any("page 2" in m for m in bad)


def test_p001_fires_on_freed_trash_page():
    pool = PagePool(8)
    pool._rc[0] = 0
    pool._free.append(0)
    bad = check_invariants(pool)
    assert sum("trash page" in m for m in bad) == 2


def test_p001_fires_on_table_mismatch():
    pool = PagePool(8)
    p = pool.alloc()
    bad = check_invariants(pool, tables=[[p], [p]])  # two holders, rc == 1
    assert any(f"page {p}" in m for m in bad)


def test_p001_silent_on_healthy_workload():
    pool = PagePool(8)
    radix = RadixCache(2, pool)
    a = [pool.alloc(), pool.alloc()]
    radix.insert([1, 2, 3, 4], a)
    assert check_invariants(pool, radix, [a]) == []
    for p in a:
        pool.decref(p)
    radix.evict(pool.n_pages)
    assert check_invariants(pool, radix, []) == []


# ---------------------------------------------------------------------------
# R001: resilience-branch reachability (mutation-tested like every rule)
# ---------------------------------------------------------------------------

def test_r001_silent_on_healthy_engine():
    from repro.analysis import check_resilience

    report = Report()
    check_resilience(report)
    assert [f for f in report.findings if f.rule == "R001"] == []
    assert "resilience scenarios" in report.checked


def test_r001_fires_when_deadline_expiry_disconnected(monkeypatch):
    """A refactor that stops calling (or no-ops) Scheduler.expire must be
    caught: DEADLINE becomes unreachable and its counter never moves."""
    from repro.analysis import check_resilience
    from repro.serving.engine import Scheduler

    monkeypatch.setattr(Scheduler, "expire",
                        lambda self, now, stats: None)
    report = Report()
    check_resilience(report)
    msgs = [f.message for f in report.findings if f.rule == "R001"]
    assert any("DEADLINE" in m for m in msgs)
    assert any("deadline_expired" in m for m in msgs)


def test_r001_fires_when_cancel_disconnected(monkeypatch):
    from repro.analysis import check_resilience
    from repro.serving.engine import Scheduler

    monkeypatch.setattr(Scheduler, "cancel",
                        lambda self, rid, now, stats: False)
    report = Report()
    check_resilience(report)
    msgs = [f.message for f in report.findings if f.rule == "R001"]
    assert any("CANCELLED" in m for m in msgs)


# ---------------------------------------------------------------------------
# Report plumbing
# ---------------------------------------------------------------------------

def test_report_disable_and_exit_codes(tmp_path):
    r = Report(disabled=["J001"])
    r.add(Finding("J001", "suppressed"))
    r.add(Finding("J002", "kept"))
    assert [f.rule for f in r.findings] == ["J002"]
    assert r.exit_code(strict=True) == 1
    assert Report().exit_code(strict=True) == 0
    p = tmp_path / "report.json"
    r.dump(str(p))
    import json
    data = json.loads(p.read_text())
    assert data["findings"][0]["rule"] == "J002"
    assert set(data["rules"]) == set(RULES)


def test_unknown_rule_rejected_by_cli():
    from repro.analysis.__main__ import main
    with pytest.raises(SystemExit):
        main(["--disable", "XXXX"])


def test_list_rules_cli(capsys):
    from repro.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


# ---------------------------------------------------------------------------
# Regression: the genuine findings this checker surfaced
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["olmo-1b", "gemma3-4b"])
@pytest.mark.parametrize("quant", ["none", "w8a8"])
def test_logits_reach_sampler_in_f32(name, quant):
    """lm_logits must return f32 even on bf16-compute / w8a8 configs (the
    sampler's argmax ties and top-k tails resolve on full-precision values).
    This was a genuine finding: the head GEMM used to return compute_dtype."""
    from repro.analysis.runner import analysis_config

    cfg = analysis_config(name, "reference", quant)
    assert cfg.compute_dtype == jnp.bfloat16  # the trap this guards against
    params = M.init(cfg, jax.random.PRNGKey(0))
    if quant == "w8a8":
        params = M.quantize_params(cfg, params)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}

    def fwd(p, b):
        hidden, _, _ = M.forward_hidden(cfg, p, b, mode="train")
        return M.lm_logits(cfg, p, hidden)

    out = jax.eval_shape(fwd, params, batch)
    assert out.dtype == jnp.float32


def test_bf16_forward_has_no_unaccumulated_dots():
    """Regression: every bf16 einsum/dot accumulates in f32 (J002-clean)."""
    from repro.analysis.runner import analysis_config

    cfg = analysis_config("gemma3-4b", "reference", "none")
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}

    def fwd(p, b):
        hidden, _, _ = M.forward_hidden(cfg, p, b, mode="train")
        return M.lm_logits(cfg, p, hidden)

    fs = lint_of(fwd, params, batch)
    assert [f for f in fs if f.rule == "J002"] == []


def test_analysis_smoke_single_config():
    """End-to-end: the checker runs clean on one real config cell and the
    report carries the checked surfaces."""
    from repro.analysis import run_analysis

    report = run_analysis(configs=["olmo-1b"], modes=("reference",),
                          quants=("none",))
    assert report.findings == []
    assert any("entry=decode" in c for c in report.checked)
    assert any("kernel=" in c for c in report.checked)
    assert any("paging" in c for c in report.checked)
