"""End-to-end serving example: requests stream through the continuous-batching
engine — each is prefilled into pages of the shared KV pool, decodes inside
the scanned multi-token loop, and releases its pages for the next arrival
(common prompt prefixes share pages via the radix cache).

    PYTHONPATH=src python examples/serve.py --arch gemma3-4b --max-new 24

``--no-reduced`` serves the published widths (a TPU's work); the default
serves ``reduce_config(arch)``, which runs in seconds on a CPU.
"""
import argparse

import jax

from repro.configs import get_config, reduce_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving import (Engine, EngineConfig, bytes_tokenizer_decode,
                           bytes_tokenizer_encode)

REQUESTS = [
    "the paper proposes a 4x4 PE array",
    "switchless mesh torus interconnects reduce",
    "block-wise GEMM execution increases data reuse",
    "ultra low power edge inference",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve reduce_config(arch) (default); "
                         "--no-reduced serves the published widths")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--batch", type=int, default=2,
                    help="max concurrent sequences (decode batch)")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked-prefill budget per tick (default: whole "
                         "prompt in one chunk)")
    ap.add_argument("--kernel-mode", default=None,
                    choices=["reference", "interpret", "pallas"])
    ap.add_argument("--quant", default=None, choices=["none", "w8a8"],
                    help="w8a8: serve through the packed int8 GEMM kernels")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline (s); late requests retire "
                         "with finish_reason=deadline")
    ap.add_argument("--preemption", default="off",
                    choices=["off", "recompute", "drop"],
                    help="page-pressure policy (see EngineConfig.preemption)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    params = M.init(cfg, jax.random.PRNGKey(0))
    # batch of 2 for 4 requests: watch the engine recycle pages mid-flight;
    # the `with` block retires anything unfinished as CANCELLED and checks
    # the page pool reconciles on the way out
    with Engine(cfg, params, EngineConfig(
            max_len=256, max_batch=args.batch,
            chunk_tokens=args.chunk_tokens, deadline_s=args.deadline,
            preemption=args.preemption,
            kernel_mode=args.kernel_mode, quant=args.quant)) as eng:
        for i, req in enumerate(REQUESTS):
            eng.submit(bytes_tokenizer_encode(req, cfg.vocab_size),
                       max_new=args.max_new, temperature=args.temperature,
                       seed=i)
        results = {r.rid: r for r in eng.run()}

    stats = eng.stats
    print(f"arch={cfg.name} kernel_mode={eng.cfg.kernel_mode} "
          f"quant={eng.cfg.quant} requests={len(REQUESTS)} batch={args.batch} "
          f"prefill={stats.prefill_s:.2f}s decode={stats.decode_s:.2f}s "
          f"({stats.tokens_per_s:.1f} tok/s, "
          f"prefix_hit={eng.prefix_hit_rate:.0%})")
    for rid, req in enumerate(REQUESTS):
        r = results[rid]
        gen = bytes_tokenizer_decode(r.generated)
        print(f"  [{req[:40]:40s}] ({r.finish_reason.value}) -> {gen!r}")


if __name__ == "__main__":
    main()
