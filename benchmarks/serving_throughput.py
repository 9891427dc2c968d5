"""E7: serving throughput — paged continuous-batching engine vs the seed
per-token Python loop, plus the paged cache's headline capacity win.

Workloads:
- closed batch: same requests all present at t=0, head-to-head tokens/s vs
  the seed-style loop (one fixed batch, Python `for` over decode steps)
- streaming: Poisson arrivals through a small engine (p50/p99 latency)
- prefix reuse: N requests sharing a long common prompt prefix, served at a
  *fixed KV memory budget* — radix page sharing vs no sharing.  Reported:
  prefix-cache hit rate and the max concurrent sequences each mode reaches
  (the paged+radix engine fits the whole batch where slot-equivalent
  allocation fits a fraction).
- goodput under SLO: a Poisson stream of short requests with a 512+-token
  prompt injected mid-stream, chunked prefill (``chunk_tokens=32``) vs
  unchunked — TTFT p50/p99, inter-token-latency p99, and the fraction of
  requests meeting ``--slo-ttft``/``--slo-itl``.  Unchunked, the long
  prefill head-of-line-blocks every in-flight decode for its whole
  duration (an ITL spike); chunked, it streams through the mixed step 32
  tokens per tick and decodes keep flowing.
- degraded mode: a 2x-oversubscribed Poisson burst against a deliberately
  small page pool, ``preemption="recompute"`` vs ``"off"`` — goodput
  (healthy completions/s) plus preemption / rejection / deadline-expiry
  rates.  Recompute admits on prompt-only reservations and resolves pool
  pressure by preempt-and-recompute; off reserves fully up front and sheds
  the same load at the bounded queue instead.
- mesh scaling: closed-batch tokens/s plus compiled-HLO
  bytes-accessed-per-decode-token at mesh widths 1/2/4/8, over the devices
  this process has (on CPU, start it with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; host-CPU shards
  share the physical core pool, so bytes moved — not tokens/s — is the
  scaling signal there).

``--json PATH`` additionally dumps the headline numbers (tokens/s, prefix
hit rate, concurrency at fixed memory, goodput/TTFT/ITL chunked vs
unchunked) for CI to persist.

    PYTHONPATH=src python benchmarks/serving_throughput.py [--arch olmo-1b]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.models import model as M
from repro.serving import (Engine, EngineConfig, ServeStats,
                           bytes_tokenizer_encode)

MAX_NEW = 32
N_REQUESTS = 8


def make_workload(cfg, n=N_REQUESTS, seed=0):
    """Mixed prompt lengths, 4..70 bytes."""
    rng = np.random.RandomState(seed)
    return [bytes_tokenizer_encode(f"req {i}: " + "lorem " * rng.randint(1, 12),
                                   cfg.vocab_size) for i in range(n)]


def seed_generate(cfg, params, prompts, max_new=MAX_NEW):
    """The seed engine's decode path: one fixed batch, prefill padded to the
    longest prompt, then a Python loop dispatching one compiled step per
    token (cache capacity pre-padded via ``prefill(cache_len=...)``)."""
    plen = max(len(p) for p in prompts)
    dec = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    pre = jax.jit(lambda p, b: M.prefill(cfg, p, b, cache_len=plen + max_new))
    B = len(prompts)
    toks = np.zeros((B, plen), np.int32)
    for i, p in enumerate(prompts):
        toks[i, plen - len(p):] = p
    stats = ServeStats()
    t0 = time.time()
    logits, caches = pre(params, {"tokens": jnp.asarray(toks)})
    jax.block_until_ready(caches)
    stats.prefill_s = time.time() - t0
    out = [list(p) for p in prompts]
    cur = jnp.argmax(logits[:, -1, : cfg.vocab_size], -1).astype(jnp.int32)
    t0 = time.time()
    for step in range(max_new):
        for i in range(B):
            out[i].append(int(cur[i]))
        if step < max_new - 1:
            logits, caches = dec(params, caches, cur[:, None],
                                 jnp.int32(plen + step))
            cur = jnp.argmax(logits[:, -1, : cfg.vocab_size], -1).astype(jnp.int32)
    stats.decode_s = time.time() - t0
    stats.tokens_out = B * max_new
    return out, stats


def bench_closed_batch(cfg, params, prompts):
    """Head-to-head: same 8 requests, all present at t=0."""
    # warm both paths (compile), then time a fresh run
    seed_generate(cfg, params, prompts)
    t0 = time.time()
    _, seed_stats = seed_generate(cfg, params, prompts)
    seed_wall = time.time() - t0

    eng = Engine(cfg, params, EngineConfig(max_len=256,
                                           max_batch=len(prompts),
                                           decode_chunk=8))
    eng.generate(prompts, max_new=MAX_NEW)  # warm (compile)
    t0 = time.time()
    _, cb_stats = eng.generate(prompts, max_new=MAX_NEW)  # per-call deltas
    cb_wall = time.time() - t0
    return seed_stats, seed_wall, cb_stats, cb_wall


def bench_streaming(cfg, params, prompts, rate=4.0):
    """Poisson arrivals at `rate` req/s through a 4-row engine."""
    rng = np.random.RandomState(1)
    eng = Engine(cfg, params, EngineConfig(max_len=256, max_batch=4,
                                           decode_chunk=8))
    eng.generate(prompts[:4], max_new=4)  # warm compiles
    due = np.cumsum(rng.exponential(1.0 / rate, len(prompts)))
    t0, nxt, results = time.time(), 0, []
    while nxt < len(prompts) or eng.num_queued or eng.num_active:
        now = time.time() - t0
        while nxt < len(prompts) and now >= due[nxt]:
            eng.submit(prompts[nxt], MAX_NEW, seed=nxt)
            nxt += 1
        if not (eng.num_queued or eng.num_active):
            time.sleep(min(0.01, max(0.0, due[nxt] - now)))
            continue
        results.extend(eng.step())
    wall = time.time() - t0
    lat = sorted(r.latency_s for r in results)
    ttft = sorted(r.ttft_s for r in results)
    toks = sum(len(r.generated) for r in results)
    return dict(wall=wall, toks=toks, tput=toks / wall,
                p50=lat[len(lat) // 2], p99=lat[-1],
                ttft_p50=ttft[len(ttft) // 2])


def bench_prefix_reuse(cfg, params, n_req=8, prefix_len=512, suffix_len=8,
                       max_new=8, page_size=64):
    """N requests sharing a ``prefix_len``-token prompt prefix, at a fixed
    page-pool budget sized so sharing is the difference between fitting the
    whole batch and fitting a fraction of it."""
    rng = np.random.RandomState(2)
    prefix = rng.randint(1, cfg.vocab_size, prefix_len).tolist()
    prompts = [prefix + rng.randint(1, cfg.vocab_size, suffix_len).tolist()
               for _ in range(n_req)]
    rows = prefix_len + suffix_len + max_new
    pages_per_req = -(-rows // page_size)
    # budget: the shared prefix once + one private tail page per request
    n_pages = 1 + (prefix_len // page_size) + n_req * (
        pages_per_req - prefix_len // page_size)
    max_len = -(-rows // page_size) * page_size
    out = {}
    for label, use_prefix in (("radix", True), ("no_share", False)):
        eng = Engine(cfg, params, EngineConfig(
            max_len=max_len, max_batch=n_req, page_size=page_size,
            n_pages=n_pages, prefix_cache=use_prefix, decode_chunk=8))
        t0 = time.time()
        eng.generate(prompts, max_new=max_new)
        out[label] = dict(wall=time.time() - t0,
                          max_concurrent=eng.stats.peak_active,
                          hit_rate=eng.prefix_hit_rate)
    out["kv_rows_budget"] = (n_pages - 1) * page_size
    return out


def bench_degraded(cfg, params, preemption, *, n_req=16, rate=400.0,
                   max_new=24, page_size=16, n_pages=10, max_batch=6,
                   max_queue=4, deadline_s=5.0, seed=9):
    """2x-oversubscribed Poisson burst against a deliberately small page
    pool (9 usable pages vs an 18-page full-reservation demand at
    ``max_batch``).  ``preemption="recompute"`` admits on prompt-only
    reservations and resolves pool pressure by preempt-and-recompute;
    ``"off"`` reserves fully up front and sheds the same load at the
    bounded queue.  Nothing is silently dropped either way — every request
    comes back with a :class:`FinishReason`."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, rng.randint(12, 28)).tolist()
               for _ in range(n_req)]
    eng = Engine(cfg, params, EngineConfig(
        max_len=4 * page_size, max_batch=max_batch, page_size=page_size,
        n_pages=n_pages, decode_chunk=4, chunk_tokens=16,
        max_queue=max_queue, deadline_s=deadline_s, preemption=preemption))
    eng.generate(prompts[:2], max_new=4)  # warm compiles
    base = (eng.stats.preempted, eng.stats.rejected,
            eng.stats.deadline_expired)
    due = np.cumsum(rng.exponential(1.0 / rate, n_req))
    t0, nxt, results = time.time(), 0, []
    while nxt < n_req or eng.num_queued or eng.num_active:
        now = time.time() - t0
        while nxt < n_req and now >= due[nxt]:
            eng.submit(prompts[nxt], max_new, seed=nxt)
            nxt += 1
        if not (eng.num_queued or eng.num_active):
            time.sleep(min(0.01, max(0.0, due[nxt] - now)))
            continue
        results.extend(eng.step())
    results.extend(eng.run())
    wall = time.time() - t0
    ok = [r for r in results if r.ok]
    return dict(
        wall=wall, total=len(results), completed=len(ok),
        goodput_req_s=len(ok) / wall,
        goodput_tok_s=sum(len(r.generated) for r in ok) / wall,
        preempted=eng.stats.preempted - base[0],
        rejected=eng.stats.rejected - base[1],
        deadline_expired=eng.stats.deadline_expired - base[2])


def bench_mesh_scaling(arch: str) -> dict:
    """Closed-batch throughput and compiled decode bytes-per-token at mesh
    widths 1/2/4/8, in this process, over the devices it has: the chips of a
    TPU host, or host CPU devices forced before the process starts
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  Widths past
    the device count are not run."""
    from repro.serving import MeshSpec
    cfg = reduce_config(get_config(arch))
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = make_workload(cfg, n=4)
    out = {"devices": jax.device_count(),
           "device_kind": jax.devices()[0].device_kind, "widths": {}}
    for m in (1, 2, 4, 8):
        if m > jax.device_count():
            continue
        eng = Engine(cfg, params, EngineConfig(
            max_len=256, max_batch=4, decode_chunk=4,
            mesh=None if m == 1 else MeshSpec(1, m)))
        eng.generate(prompts, max_new=8)                 # warm (compile)
        t0 = time.time()
        _, stats = eng.generate(prompts, max_new=MAX_NEW)
        wall = time.time() - t0
        runner, sched = eng.runner, eng.sched
        lowered = runner.decode_fn.lower(
            runner.params, runner.caches, jnp.asarray(sched.pages),
            jnp.asarray(sched.cur), jnp.asarray(sched.pos),
            jnp.asarray(sched.remaining), jnp.asarray(sched.temp),
            jnp.asarray(sched.keys),
            jnp.zeros(eng.config.max_batch, jnp.bool_))
        ca = lowered.compile().cost_analysis()
        toks_per_call = eng.config.decode_chunk * eng.config.max_batch
        out["widths"][str(m)] = dict(
            decode_tokens_per_s=round(stats.tokens_per_s, 2),
            end_to_end_tokens_per_s=round(4 * MAX_NEW / wall, 2),
            decode_bytes_per_token=round(
                float(ca.get("bytes accessed", 0.0)) / toks_per_call),
        )
    return out


def _pctl(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0


def bench_goodput(cfg, params, chunk_tokens, *, slo_ttft_s=2.0,
                  slo_itl_s=0.25, rate=50.0, n_short=12, long_len=560,
                  max_new=16, seed=3):
    """Poisson stream of short requests with one ``long_len``-token prompt
    injected mid-stream; measures what chunked prefill buys the *other*
    requests: TTFT/ITL tails and SLO-goodput."""
    rng = np.random.RandomState(seed)
    shorts = [rng.randint(1, cfg.vocab_size, rng.randint(8, 40)).tolist()
              for _ in range(n_short)]
    long_prompt = rng.randint(1, cfg.vocab_size, long_len).tolist()
    eng = Engine(cfg, params, EngineConfig(
        max_len=long_len + max_new, max_batch=8, page_size=64,
        chunk_tokens=chunk_tokens, decode_chunk=4,
        slo_ttft_s=slo_ttft_s, slo_itl_s=slo_itl_s))
    # warm both tick shapes (compile): a long prefill and a short batch
    eng.generate([long_prompt], max_new=2)
    eng.generate(shorts[:4], max_new=4)

    due = np.cumsum(rng.exponential(1.0 / rate, n_short)).tolist()
    arrivals = sorted([(t, p) for t, p in zip(due, shorts)] +
                      [(due[n_short // 3], long_prompt)])
    t0, nxt, results = time.time(), 0, []
    while nxt < len(arrivals) or eng.num_queued or eng.num_active:
        now = time.time() - t0
        while nxt < len(arrivals) and now >= arrivals[nxt][0]:
            eng.submit(arrivals[nxt][1], max_new, seed=nxt)
            nxt += 1
        if not (eng.num_queued or eng.num_active):
            time.sleep(min(0.01, max(0.0, arrivals[nxt][0] - now)))
            continue
        results.extend(eng.step())
    wall = time.time() - t0

    ttft = [r.ttft_s for r in results]
    itl = [g for r in results for g in r.itl_s]
    good = sum(1 for r in results
               if r.ttft_s <= slo_ttft_s
               and all(g <= slo_itl_s for g in r.itl_s))
    toks = sum(len(r.generated) for r in results)
    return dict(wall=wall, toks=toks, tput=toks / wall,
                ttft_p50=_pctl(ttft, 0.5), ttft_p99=_pctl(ttft, 0.99),
                itl_p99=_pctl(itl, 0.99),
                goodput_frac=good / len(results),
                goodput_req_s=good / wall)


def run(arch: str = "olmo-1b", slo_ttft_s: float = 2.0,
        slo_itl_s: float = 0.25) -> tuple[list[str], dict]:
    cfg = reduce_config(get_config(arch))
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = make_workload(cfg)
    out = [f"# E7 serving throughput ({cfg.name}, {N_REQUESTS} mixed-length "
           f"requests x {MAX_NEW} new tokens)"]

    seed_stats, seed_wall, cb_stats, cb_wall = bench_closed_batch(
        cfg, params, prompts)
    out.append("engine,decode_tok_s,end_to_end_tok_s,wall_s")
    n_tok = N_REQUESTS * MAX_NEW
    out.append(f"seed_loop,{seed_stats.tokens_per_s:.1f},"
               f"{n_tok / seed_wall:.1f},{seed_wall:.2f}")
    out.append(f"continuous_scan,{cb_stats.tokens_per_s:.1f},"
               f"{n_tok / cb_wall:.1f},{cb_wall:.2f}")
    speedup = seed_wall / cb_wall
    out.append(f"derived: scan-based continuous batching is {speedup:.2f}x the "
               f"seed loop end-to-end (per-step Python dispatch and cache "
               f"re-padding eliminated)")

    s = bench_streaming(cfg, params, prompts)
    out.append("streaming (Poisson 4 req/s, 4 batch rows): "
               f"{s['tput']:.1f} tok/s p50={s['p50']:.2f}s p99={s['p99']:.2f}s "
               f"ttft_p50={s['ttft_p50']:.2f}s")

    SLO_TTFT, SLO_ITL = slo_ttft_s, slo_itl_s
    gp = {label: bench_goodput(cfg, params, ct, slo_ttft_s=SLO_TTFT,
                               slo_itl_s=SLO_ITL)
          for label, ct in (("chunked", 32), ("unchunked", None))}
    out.append(f"goodput under SLO (ttft<={SLO_TTFT}s, itl<={SLO_ITL}s; "
               f"Poisson shorts + one 560-token prompt mid-stream):")
    for label, g in gp.items():
        out.append(f"  {label}: goodput={g['goodput_frac']:.0%} "
                   f"ttft_p50={g['ttft_p50']:.3f}s "
                   f"ttft_p99={g['ttft_p99']:.3f}s "
                   f"itl_p99={g['itl_p99']:.3f}s {g['tput']:.1f} tok/s")
    out.append(f"derived: chunked prefill cuts inter-token p99 "
               f"{gp['unchunked']['itl_p99'] / max(gp['chunked']['itl_p99'], 1e-9):.1f}x "
               f"(the long prefill no longer head-of-line-blocks decodes)")

    dg = {mode: bench_degraded(cfg, params, mode)
          for mode in ("recompute", "off")}
    out.append("degraded mode (2x-oversubscribed Poisson burst, 9-page "
               "pool, bounded queue):")
    for mode, d in dg.items():
        out.append(f"  preemption={mode}: goodput={d['goodput_req_s']:.1f} "
                   f"req/s ({d['goodput_tok_s']:.1f} tok/s) "
                   f"completed={d['completed']}/{d['total']} "
                   f"preempted={d['preempted']} rejected={d['rejected']} "
                   f"deadline={d['deadline_expired']}")
    out.append("derived: recompute-preemption trades repeat prefill work "
               "(cheap — radix hits cover the recompute) for admission at "
               "prompt-only reservations; full up-front reservation sheds "
               "the same burst at the bounded queue instead")

    pr = bench_prefix_reuse(cfg, params)
    out.append(f"prefix reuse (8 reqs sharing a 512-token prefix, "
               f"{pr['kv_rows_budget']} KV rows total): "
               f"radix max_concurrent={pr['radix']['max_concurrent']} "
               f"hit_rate={pr['radix']['hit_rate']:.2f} | no_share "
               f"max_concurrent={pr['no_share']['max_concurrent']}")

    ms = bench_mesh_scaling(arch)
    out.append(f"mesh scaling ({ms['devices']} x {ms['device_kind']}, 1xM "
               f"model-parallel, 4 reqs x {MAX_NEW} new tokens; bytes from "
               f"compiled decode HLO cost analysis):")
    out.append("  mesh,decode_tok_s,end_to_end_tok_s,decode_bytes_per_tok")
    for m, row in sorted(ms["widths"].items(), key=lambda kv: int(kv[0])):
        out.append(f"  1x{m},{row['decode_tokens_per_s']},"
                   f"{row['end_to_end_tokens_per_s']},"
                   f"{row['decode_bytes_per_token']}")
    out.append("derived: host-CPU mesh widths share one physical core "
               "pool, so there tokens/s measures sharding overhead, not "
               "speedup; bytes-per-token is the signal (per-device weight "
               "traffic should fall as 1/M for the sharded projections)")

    blob = dict(
        arch=cfg.name,
        decode_tokens_per_s=round(cb_stats.tokens_per_s, 2),
        seed_decode_tokens_per_s=round(seed_stats.tokens_per_s, 2),
        end_to_end_speedup=round(speedup, 3),
        streaming_p50_s=round(s["p50"], 3),
        streaming_p99_s=round(s["p99"], 3),
        prefix_hit_rate=round(pr["radix"]["hit_rate"], 4),
        max_concurrent_radix=pr["radix"]["max_concurrent"],
        max_concurrent_no_share=pr["no_share"]["max_concurrent"],
        kv_rows_budget=pr["kv_rows_budget"],
        slo_ttft_s=SLO_TTFT,
        slo_itl_s=SLO_ITL,
        chunked_ttft_p50_s=round(gp["chunked"]["ttft_p50"], 4),
        chunked_ttft_p99_s=round(gp["chunked"]["ttft_p99"], 4),
        chunked_itl_p99_s=round(gp["chunked"]["itl_p99"], 4),
        chunked_goodput_frac=round(gp["chunked"]["goodput_frac"], 4),
        unchunked_ttft_p50_s=round(gp["unchunked"]["ttft_p50"], 4),
        unchunked_ttft_p99_s=round(gp["unchunked"]["ttft_p99"], 4),
        unchunked_itl_p99_s=round(gp["unchunked"]["itl_p99"], 4),
        unchunked_goodput_frac=round(gp["unchunked"]["goodput_frac"], 4),
        degraded={mode: {k: (round(v, 4) if isinstance(v, float) else int(v))
                         for k, v in d.items()}
                  for mode, d in dg.items()},
        mesh_scaling=ms,
    )
    return out, blob


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--json", default=None,
                    help="also dump headline numbers to this JSON path")
    ap.add_argument("--slo-ttft", type=float, default=2.0,
                    help="time-to-first-token SLO (s) for goodput")
    ap.add_argument("--slo-itl", type=float, default=0.25,
                    help="inter-token-latency SLO (s) for goodput")
    args = ap.parse_args()
    lines, blob = run(args.arch, slo_ttft_s=args.slo_ttft,
                      slo_itl_s=args.slo_itl)
    print("\n".join(lines))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
