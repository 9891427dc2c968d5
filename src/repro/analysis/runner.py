"""Drive the static checks over every shipped config.

For each (config, kernel_mode, quant) cell the runner *traces* — never
executes — the real serving entry points (``jax.make_jaxpr`` on the same
bound methods the engine jits) and walks the jaxprs with the J-rules, checks
buffer donation on the jitted surfaces (D-rules), proves the BlockSpec
contracts of every Pallas kernel the config can reach (K-rules, via the
kernels' introspectable ``KernelSpec``), and exercises the paging
bookkeeping against ``paging.check_invariants`` (P001).

Configs are shrunk with ``reduce_config`` for trace speed but keep their
*shipped* dtypes (``reduce_config`` forces f32, which would hide every
promotion bug this tool exists to catch) and the requested kernel mode and
quantization."""
from __future__ import annotations

from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.bounds import check_kernel_spec
from repro.analysis.donation import check_donation
from repro.analysis.findings import Finding, Report
from repro.analysis.hlo_lints import lint_hlo, param_gather_shapes
from repro.analysis.jaxpr_lints import check_logits_dtype, lint_jaxpr
from repro.configs import REGISTRY, get_config, reduce_config
from repro.models import model as M
from repro.serving.config import EngineConfig
from repro.serving.engine import Engine
from repro.serving.paging import PagePool, RadixCache, check_invariants

MODES = ("reference", "interpret")
QUANTS = ("none", "w8a8")

# trace geometry: small enough to trace fast, big enough to exercise every
# structural path (window=32 after reduce_config, one page table per seq)
_S = 32          # forward / prefill sequence length
_B = 2           # batch
_ENGINE = dict(page_size=16, max_batch=2, max_len=64, decode_chunk=2)


def analysis_config(name: str, mode: str, quant: str):
    """Reduced config with the *shipped* dtypes / kernel mode / quant.

    ``reduce_config`` forces f32 params+compute for numeric smoke tests;
    the checker restores the original dtypes — a bf16 serving stack traced
    in f32 would show none of the promotions the J-rules look for."""
    full = get_config(name)
    return reduce_config(full).with_(
        param_dtype=full.param_dtype,
        compute_dtype=full.compute_dtype,
        kernel_mode=mode,
        quant=quant,
    )


def _batch(cfg, B: int = _B, S: int = _S, labels: bool = False) -> dict:
    rng = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if labels:
        batch["labels"] = batch["tokens"]
    if cfg.audio_frontend:
        batch["frames"] = jnp.zeros((B, S, cfg.frontend_dim), jnp.float32)
    if cfg.vision_tokens:
        batch["images"] = jnp.zeros((B, cfg.vision_tokens, cfg.vision_dim),
                                    jnp.float32)
    return batch


def _lint_entry(report: Report, fn, args, ctx: str, *, logits: bool = False,
                donate: Optional[tuple] = None) -> None:
    """Trace one entry point and run the J (and optionally D/J006) rules."""
    closed = jax.make_jaxpr(fn)(*args)
    report.extend(lint_jaxpr(closed, ctx))
    if logits:
        report.extend(check_logits_dtype(closed.jaxpr.outvars[0].aval, ctx))
    if donate is not None:
        report.extend(check_donation(fn, args, donate, ctx))
    report.checked.append(ctx)


def check_cell(name: str, mode: str, quant: str, report: Report,
               params=None) -> None:
    """All jaxpr/donation checks for one (config, mode, quant) cell."""
    cfg = analysis_config(name, mode, quant)
    base = f"config={name} mode={mode} quant={quant}"
    if params is None:
        params = M.init(cfg, jax.random.PRNGKey(0))

    # forward (train) entry — every config, encoder included
    fwd_params = (M.quantize_params(cfg, params) if quant == "w8a8"
                  else params)

    def fwd(p, batch):
        hidden, _, _ = M.forward_hidden(cfg, p, batch, mode="train")
        return M.lm_logits(cfg, p, hidden)

    _lint_entry(report, fwd, (fwd_params, _batch(cfg)),
                f"{base} entry=forward", logits=True)

    if cfg.kind != "decoder":
        return

    # serving entries, traced exactly as the engine jits them
    eng = Engine(cfg, params, EngineConfig(kernel_mode=mode, quant=quant,
                                           **_ENGINE))
    runner, npp = eng.runner, eng.npp
    caches = runner.caches
    pages = jnp.zeros((_B, npp), jnp.int32)
    cur = jnp.zeros(_B, jnp.int32)
    pos = jnp.zeros(_B, jnp.int32)
    remaining = jnp.zeros(_B, jnp.int32)
    temp = jnp.zeros(_B, jnp.float32)
    keys = jnp.zeros((_B, 2), jnp.uint32)

    def pfx(p, batch):
        return M.prefill(eng.cfg, p, batch)[0]

    _lint_entry(report, pfx, (runner.params, _batch(eng.cfg)),
                f"{base} entry=prefill", logits=True)

    nanmask = jnp.zeros(_B, jnp.bool_)
    dec_args = (runner.params, caches, pages, cur, pos, remaining, temp, keys,
                nanmask)
    _lint_entry(report, runner._decode_chunk, dec_args,
                f"{base} entry=decode", donate=(1,))
    report.extend(check_logits_dtype(
        jax.eval_shape(lambda: M.decode_step(
            eng.cfg, runner.params, caches, cur[:, None], pos,
            pages=pages)[0]),
        f"{base} entry=decode"))

    _lint_entry(report, runner._copy_page,
                (caches, jnp.int32(1), jnp.int32(2)),
                f"{base} entry=copy_page", donate=(0,))

    if eng.sched.chunked:
        C = 8
        mixed_args = (runner.params, caches, jnp.zeros((1, C), jnp.int32),
                      pages[:1], jnp.int32(0), jnp.int32(C), jnp.float32(0.0),
                      keys[0], jnp.bool_(False), pages, cur, pos, remaining,
                      temp, keys, nanmask)
        _lint_entry(report, runner._mixed, mixed_args,
                    f"{base} entry=mixed", donate=(1,))
    elif all(sp.mixer != "cross" for sp in eng.cfg.layer_specs()):
        n = 8
        wp_args = (runner.params, caches, jnp.zeros((1, n), jnp.int32),
                   jnp.zeros(npp, jnp.int32), jnp.int32(0), jnp.float32(0.0),
                   keys[0])
        _lint_entry(report, runner._whole_prefill, wp_args,
                    f"{base} entry=whole_prefill", donate=(1,))
    else:
        # cross-attention prefill requires the image batch, which the
        # engine's tokens-only whole-prompt path cannot supply — the model's
        # prefill surface is covered above (entry=prefill traces M.prefill
        # with images)
        report.checked.append(f"{base} entry=whole_prefill (skipped: "
                              f"cross-attn prefill needs images)")


def check_sharded(name: str, report: Report, params=None) -> None:
    """Sharded-surface checks (J007 + the J/D rules on mesh traces).

    Builds the engine on a ``1xT`` model-parallel mesh over the host's
    devices, traces the serving executables with the mesh context active
    (so the jaxprs carry the real sharding constraints), and compiles the
    decode and prefill executables to run the J007 HLO lint — all-gathers
    only exist after SPMD partitioning, so the jaxpr rules cannot see
    them.  Reference mode / no quant only: kernel modes share the same
    placement code, and the compiled-module check is about *sharding*,
    not kernel internals.  Skipped (with a note) on single-device hosts;
    the multi-device CI lane forces 8 host devices."""
    dc = jax.device_count()
    cfg = analysis_config(name, "reference", "none")
    if cfg.kind != "decoder":
        return
    if dc < 2:
        report.checked.append(
            f"config={name} sharded surfaces (skipped: 1 device; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return
    tp = 8 if dc >= 8 else (4 if dc >= 4 else 2)
    base = f"config={name} mesh=1x{tp}"
    if params is None:
        params = M.init(cfg, jax.random.PRNGKey(0))

    eng = Engine(cfg, params, EngineConfig(kernel_mode="reference",
                                           quant="none", mesh=f"1x{tp}",
                                           **_ENGINE))
    runner, npp = eng.runner, eng.npp
    caches = runner.caches
    pages = jnp.zeros((_B, npp), jnp.int32)
    cur = jnp.zeros(_B, jnp.int32)
    pos = jnp.zeros(_B, jnp.int32)
    remaining = jnp.zeros(_B, jnp.int32)
    temp = jnp.zeros(_B, jnp.float32)
    keys = jnp.zeros((_B, 2), jnp.uint32)
    shapes = param_gather_shapes(runner.params)

    nanmask = jnp.zeros(_B, jnp.bool_)
    dec_args = (runner.params, caches, pages, cur, pos, remaining, temp, keys,
                nanmask)
    _lint_entry(report, runner._traced(runner._decode_chunk), dec_args,
                f"{base} entry=decode", donate=(1,))
    hlo = runner.decode_fn.lower(*dec_args).compile().as_text()
    report.extend(lint_hlo(hlo, shapes, f"{base} entry=decode hlo"))
    report.checked.append(f"{base} entry=decode hlo")

    if eng.sched.chunked:
        C = 8
        mixed_args = (runner.params, caches, jnp.zeros((1, C), jnp.int32),
                      pages[:1], jnp.int32(0), jnp.int32(C), jnp.float32(0.0),
                      keys[0], jnp.bool_(False), pages, cur, pos, remaining,
                      temp, keys, nanmask)
        _lint_entry(report, runner._traced(runner._mixed), mixed_args,
                    f"{base} entry=mixed", donate=(1,))
        hlo = runner.mixed_fn(C, 1).lower(*mixed_args).compile().as_text()
        report.extend(lint_hlo(hlo, shapes, f"{base} entry=mixed hlo"))
        report.checked.append(f"{base} entry=mixed hlo")
    elif all(sp.mixer != "cross" for sp in eng.cfg.layer_specs()):
        n = 8
        wp_args = (runner.params, caches, jnp.zeros((1, n), jnp.int32),
                   jnp.zeros(npp, jnp.int32), jnp.int32(0), jnp.float32(0.0),
                   keys[0])
        _lint_entry(report, runner._traced(runner._whole_prefill), wp_args,
                    f"{base} entry=whole_prefill", donate=(1,))
        hlo = runner.whole_prefill_fn(n, 1).lower(*wp_args).compile() \
                    .as_text()
        report.extend(lint_hlo(hlo, shapes, f"{base} entry=whole_prefill "
                                            f"hlo"))
        report.checked.append(f"{base} entry=whole_prefill hlo")


def check_kernels(name: str, report: Report) -> None:
    """K-rule bounds proofs for every kernel the config can reach.

    Mode/quant-independent: the specs describe grid/index-map geometry,
    which is fixed by the architecture + engine cache geometry."""
    from repro.kernels.block_gemm import gemm_spec
    from repro.kernels.decode_attention import fd_paged_spec
    from repro.kernels.flash_attention import fa_dense_spec, fa_paged_spec

    cfg = analysis_config(name, "reference", "none")
    ctx = f"config={name}"
    ec = EngineConfig(**_ENGINE)
    ps, npp, n_pages = ec.page_size, ec.cache_spec().pages_per_seq, ec.n_pages

    specs = [gemm_spec(cfg.d_model, cfg.d_model, cfg.vocab_size),
             gemm_spec(cfg.d_model, cfg.d_model, cfg.vocab_size, int8=True)]
    mixers = {sp.mixer for sp in cfg.layer_specs()}
    if any(m.startswith("attn") or m == "cross" for m in mixers):
        H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        specs.append(fa_dense_spec(_B, H, K, _S, _S, d))
        if cfg.kind == "decoder":
            specs.append(fa_paged_spec(_B, H, K, ps, d, ps, npp, n_pages))
            # the paged decode's head block is sized from the pool's dtype;
            # MLA decodes one fused [latent | rope] pool, read as v to kvr
            isz = jnp.dtype(cfg.compute_dtype).itemsize
            if cfg.use_mla:
                dq = cfg.kv_lora_rank + cfg.qk_rope_dim
                specs.append(fd_paged_spec(_B, H, 1, dq, cfg.kv_lora_rank,
                                           ps, npp, n_pages, itemsize=isz))
            else:
                specs.append(fd_paged_spec(_B, H, K, d, d, ps, npp, n_pages,
                                           itemsize=isz))
    for spec in specs:
        report.extend(check_kernel_spec(spec, ctx))
        report.checked.append(f"{ctx} kernel={spec.name}")


def check_paging(report: Report) -> None:
    """P001: run a deterministic alloc/share/evict workload and verify the
    structural invariants at every quiescent point."""
    ctx = "paging workload"

    def verify(step: str, pool, radix=None, tables=None) -> None:
        for msg in check_invariants(pool, radix, tables):
            report.add(Finding("P001", msg, f"{ctx} step={step}"))

    pool = PagePool(12)
    radix = RadixCache(4, pool)
    verify("init", pool, radix, [])

    # request A: 3 pages, publishes 2 full pages to the tree
    a = [pool.alloc() for _ in range(3)]
    toks_a = list(range(8))
    radix.insert(toks_a, a[:2])
    tables = [a]
    verify("insert", pool, radix, tables)

    # request B: full prefix hit on A's pages + one fresh page
    m = radix.match(toks_a + [9, 9, 9, 9], max_match=11)
    for pid in m.full_pages:
        pool.incref(pid)
    b = list(m.full_pages) + [pool.alloc()]
    tables.append(b)
    verify("match", pool, radix, tables)

    # retire A: tree keeps its pages alive at refcount >= 1
    for pid in a:
        pool.decref(pid)
    tables.remove(a)
    verify("retire", pool, radix, tables)

    # evict everything evictable, then drop the tree outright
    radix.evict(pool.n_pages)
    verify("evict", pool, radix, tables)
    radix.clear()
    for pid in b:
        pool.decref(pid)
    tables.remove(b)
    verify("clear", pool, radix, tables)
    report.checked.append(ctx)


def check_resilience(report: Report) -> None:
    """R001: every ``FinishReason`` branch in the Scheduler is reachable.

    Drives a tiny *executed* (not traced) engine on the reduced edge config
    through one canonical scenario per finish reason — healthy STOP/LENGTH,
    then deadline expiry (chaos-skewed clock), cancellation, bounded-queue
    rejection, preemption under page pressure (``preemption="drop"``) and
    NaN fault isolation — and reports a finding for any reason that never
    surfaces, plus any resilience counter that failed to move.  This is the
    rot check for the degraded-mode state machine: a refactor that silently
    disconnects one of these paths (e.g. ``expire`` never called, ``cancel``
    not wired through) fails here even if no unit test covers it."""
    from repro.serving import ChaosInjector
    from repro.serving.engine import FinishReason

    ctx = "resilience scenarios"
    cfg = reduce_config(get_config("cgra-edge"))  # f32: executed, not traced
    params = M.init(cfg, jax.random.PRNGKey(0))
    ec = dict(page_size=16, max_batch=2, max_len=64, decode_chunk=2,
              prefix_cache=False)
    prompt = list(range(1, 9))
    seen: set[FinishReason] = set()
    stats_hits: set[str] = set()

    def note(eng, results):
        seen.update(r.finish_reason for r in results)
        for f in ("preempted", "rejected", "deadline_expired", "cancelled",
                  "faults_isolated"):
            if getattr(eng.stats, f) > 0:
                stats_hits.add(f)

    # STOP needs a token the model really emits: probe it greedily first
    eng = Engine(cfg, params, EngineConfig(**ec))
    eng.submit(prompt, max_new=2)
    probe = eng.run()
    note(eng, probe)  # LENGTH (max_new exhausted, no eos configured)
    first = probe[0].generated[0]

    eng = Engine(cfg, params, EngineConfig(eos_id=first, **ec))
    eng.submit(prompt, max_new=4)
    note(eng, eng.run())  # STOP (first sampled token is the eos)

    # DEADLINE: the chaos clock jumps +1000s before the first tick
    chaos = ChaosInjector(schedule={"clock.skew": {0}}, skew_s=1000.0)
    eng = Engine(cfg, params, EngineConfig(**ec), chaos=chaos)
    eng.submit(prompt, max_new=4, deadline_s=5.0)
    note(eng, eng.run())

    # CANCELLED (queued) + REJECTED (queue bound 1)
    eng = Engine(cfg, params, EngineConfig(max_queue=1, **ec))
    rid = eng.submit(prompt, max_new=4)
    eng.submit(list(prompt), max_new=4)  # overflows the bound
    eng.cancel(rid)
    note(eng, eng.run())

    # PREEMPTED: two requests oversubscribe a 3-usable-page pool in "drop"
    eng = Engine(cfg, params, EngineConfig(n_pages=4, preemption="drop",
                                           **ec))
    eng.submit(list(range(1, 17)), max_new=20)
    eng.submit(list(range(2, 18)), max_new=20)
    note(eng, eng.run())

    # FAULT: poison the first compiled step's logits
    chaos = ChaosInjector(schedule={"logits.nan": {0}})
    eng = Engine(cfg, params, EngineConfig(**ec), chaos=chaos)
    eng.submit(prompt, max_new=4)
    note(eng, eng.run())

    for reason in FinishReason:
        if reason not in seen:
            report.add(Finding(
                "R001", f"FinishReason.{reason.name} was never produced by "
                        f"its canonical scenario", ctx))
    for f in ("preempted", "rejected", "deadline_expired", "cancelled",
              "faults_isolated"):
        if f not in stats_hits:
            report.add(Finding(
                "R001", f"ServeStats.{f} never incremented across the "
                        f"scenario suite", ctx))
    report.checked.append(ctx)


def run_analysis(configs: Optional[Sequence[str]] = None,
                 modes: Iterable[str] = MODES,
                 quants: Iterable[str] = QUANTS,
                 disabled: Iterable[str] = (),
                 progress=None) -> Report:
    """The full matrix: every named config x kernel mode x quant."""
    report = Report(disabled=sorted(disabled))
    names = list(configs) if configs else sorted(REGISTRY)
    for name in names:
        get_config(name)  # fail fast on typos
    for name in names:
        params = None
        for mode in modes:
            for quant in quants:
                if progress:
                    progress(f"tracing {name} mode={mode} quant={quant}")
                if params is None:
                    params = M.init(analysis_config(name, mode, quant),
                                    jax.random.PRNGKey(0))
                check_cell(name, mode, quant, report, params=params)
        if progress:
            progress(f"kernel bounds {name}")
        check_kernels(name, report)
        if progress and jax.device_count() >= 2:
            progress(f"sharded surfaces {name}")
        check_sharded(name, report, params=params)
    check_paging(report)
    if progress:
        progress("resilience scenarios")
    check_resilience(report)
    return report
