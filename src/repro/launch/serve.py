"""Production serving driver: continuous-batching request loop.

Streams a Poisson arrival process through the engine — requests are admitted
into pages of the shared KV pool as they free up (common prompt prefixes
share pages through the radix cache), so the decode batch stays full without
ever recompiling.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b \
        --requests 16 --max-new 32 --rate 4

``--reduced`` (the default) serves ``reduce_config(arch)``, a CPU-sized
model; ``--no-reduced`` serves the published widths, for a TPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving import Engine, EngineConfig, bytes_tokenizer_encode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve reduce_config(arch) (default); "
                         "--no-reduced serves the published widths")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = all at once")
    ap.add_argument("--batch", type=int, default=8,
                    help="max concurrent sequences (decode batch)")
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--pages", type=int, default=None,
                    help="KV page-pool size (default: batch*max_len worth)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix prefix reuse")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked-prefill budget: at most this many prompt "
                         "tokens per tick, run together with in-flight "
                         "decodes in one mixed step (default: whole-suffix "
                         "prefill)")
    ap.add_argument("--kernel-mode", default=None,
                    choices=["reference", "interpret", "pallas"],
                    help="route GEMMs/attention through the CGRA Pallas "
                         "kernels (default: config's kernel_mode)")
    ap.add_argument("--quant", default=None, choices=["none", "w8a8"],
                    help="w8a8: int8-quantize weights at load and serve "
                         "through the packed int8 GEMM kernels")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve mesh-sharded over the first D*M devices "
                         "(data x model, e.g. '1x8'; a bare 'M' means "
                         "model-parallel only).  Params/KV pools are placed "
                         "with NamedSharding; MoE configs route experts "
                         "across the model axis")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request deadline in seconds (queueing + "
                         "execution); expired requests retire "
                         "FinishReason.DEADLINE")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission queue bound; past it requests finish "
                         "immediately as REJECTED with a retry_after_s hint")
    ap.add_argument("--preemption", default="off",
                    choices=["off", "recompute", "drop"],
                    help="page-pressure policy: 'recompute' admits on "
                         "prompt-only page reservations and preempts the "
                         "lowest-priority decode on exhaustion (requeue + "
                         "bit-identical recompute); 'drop' sheds the victim "
                         "with its partial output")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="attach a seeded ChaosInjector (transient "
                         "pool.alloc / runner.mixed faults + rare NaN "
                         "logits) to exercise the degraded paths")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduce_config(get_config(args.arch)) if args.reduced \
        else get_config(args.arch)
    params = M.init(cfg, jax.random.PRNGKey(0))
    chaos = None
    if args.chaos is not None:
        from repro.serving import ChaosInjector
        chaos = ChaosInjector(seed=args.chaos,
                              rates={"pool.alloc": 0.05,
                                     "runner.mixed": 0.05,
                                     "logits.nan": 0.01})
    eng = Engine(cfg, params, EngineConfig(
        max_len=args.max_len, max_batch=args.batch, page_size=args.page_size,
        n_pages=args.pages, prefix_cache=not args.no_prefix_cache,
        chunk_tokens=args.chunk_tokens, max_queue=args.max_queue,
        deadline_s=args.deadline, preemption=args.preemption,
        kernel_mode=args.kernel_mode, quant=args.quant, mesh=args.mesh),
        chaos=chaos)

    rng = np.random.RandomState(0)
    prompts = [bytes_tokenizer_encode(f"request {i}: " + "x" * rng.randint(4, 40),
                                      cfg.vocab_size)
               for i in range(args.requests)]

    results = []
    if args.rate > 0:  # streaming arrivals
        due = np.cumsum(rng.exponential(1.0 / args.rate, len(prompts)))
        t0, nxt = time.time(), 0
        while nxt < len(prompts) or eng.num_queued or eng.num_active:
            now = time.time() - t0
            while nxt < len(prompts) and now >= due[nxt]:
                eng.submit(prompts[nxt], args.max_new, args.temperature,
                           seed=nxt)
                nxt += 1
            if not (eng.num_queued or eng.num_active):
                time.sleep(min(0.01, max(0.0, due[nxt] - now)))  # idle: wait
                continue
            results.extend(eng.step())
    else:
        for i, p in enumerate(prompts):
            eng.submit(p, args.max_new, args.temperature, seed=i)
        results = eng.run()

    results.extend(eng.close())  # drain + reconcile the paging state
    stats = eng.stats
    ok = [r for r in results if r.ok]
    lat = sorted(r.latency_s for r in ok) or [0.0]
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    wait = sorted(r.admit_s - r.arrival_s for r in results
                  if r.admit_s is not None) or [0.0]
    w50 = wait[len(wait) // 2]
    w90 = wait[min(len(wait) - 1, int(len(wait) * 0.9))]
    hits = [r.prefix_hit for r in results if r.prefix_hit is not None]
    ticks = stats.mixed_steps + stats.chunks
    mesh = eng.config.mesh
    print(f"arch={cfg.name} kernel_mode={eng.cfg.kernel_mode} "
          f"quant={eng.cfg.quant} requests={len(results)} ok={len(ok)} "
          f"batch={args.batch} pages={eng.pool.n_pages} "
          f"prefill={stats.prefill_s:.2f}s decode={stats.decode_s:.2f}s "
          f"throughput={stats.tokens_per_s:.1f} tok/s "
          f"prefix_hit={eng.prefix_hit_rate:.0%} "
          f"p50={p50:.2f}s p99={p99:.2f}s "
          f"queue_wait_p50={w50:.3f}s queue_wait_p90={w90:.3f}s "
          f"host={1e3 * stats.host_s / max(ticks, 1):.2f}ms/tick "
          f"prefix_rows/req={sum(hits) / max(len(hits), 1):.1f} "
          f"mesh={f'{mesh.data}x{mesh.model}' if mesh else 'none'} "
          f"tp={eng.runner.tp} "
          f"collective={stats.collective_bytes / 1e6 / max(ticks, 1):.3f}"
          f"MB/tick")
    if (stats.preempted or stats.rejected or stats.deadline_expired
            or stats.cancelled or stats.faults_isolated):
        print(f"degraded: preempted={stats.preempted} "
              f"rejected={stats.rejected} "
              f"deadline_expired={stats.deadline_expired} "
              f"cancelled={stats.cancelled} "
              f"faults_isolated={stats.faults_isolated}")


if __name__ == "__main__":
    main()
