"""Chip smoke run: serve full-width olmo-1b on a TPU through the compiled
Pallas kernels, and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-sharded path, four chips

One chip: builds olmo-1b at its published widths (16 layers, d_model 2048,
vocab 50304, bf16) with random weights from ``--seed`` and serves 8 greedy
requests of 64-768 seeded prompt tokens through ``Engine`` with
``kernel_mode="pallas"``, paged KV and chunked prefill.  Two prompts share a
200-token prefix, so the radix cache and its copy-on-write page share run.
It checks that

- every request finishes STOP or LENGTH and ``Engine.close()`` reconciles
  the page pool;
- the compiled mixed step and decode chunk contain ``tpu_custom_call``: the
  Pallas kernels are in the program, not a reference or interpret path;
- the logits of one prompt's chunked prefill plus teacher-forced paged
  decode steps agree with ``kernel_mode="reference"`` in bf16, and with a
  float32 reference under ``jax.default_matmul_precision("highest")``,
  within :func:`logit_tol`.

``--chips 4`` serves the same requests with ``mesh="1x4"`` and on device 0
alone, in this one process, and compares the two placements' logits within
:func:`logit_tol`; it runs no other phase.

Times and memory printed on the way are one smoke run, not a benchmark.
The last line of output is ``{"ok": true, "device": {...}}``.  A failed
phase raises, so the script exits non-zero and prints no such line.  There
is no CPU fallback: without a TPU it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from contextlib import nullcontext
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "olmo-1b"
N_REQUESTS = 8
MAX_NEW = 32
PROMPT_LENS = (64, 768)
# longer than one 64-row page and not page-aligned: the second sharer maps
# three full pages of the first and copies the fourth (copy-on-write)
SHARED_PREFIX = 200
SERVE = dict(max_batch=8, max_len=1024, page_size=64, chunk_tokens=256)
N_FORCED = 4  # teacher-forced decode steps in the logits comparison


def logit_tol(cfg) -> float:
    """Bound on the relative RMS difference of two implementations' logits.

    Each layer rounds its sublayer outputs and the residual stream to the
    compute dtype, a relative error of about its unit roundoff ``u``
    (``eps / 2``).  Errors of independent layers add in quadrature, so the
    hidden state of an ``L``-layer stack carries ``sqrt(L) * u``, and two
    implementations that round differently differ by ``sqrt(2 L) * u``.  The
    bound allows 4x that: olmo-1b in bf16 gives 0.088.  A wrong page, head
    or mask moves the logits by their own size, a relative error near 1."""
    import jax.numpy as jnp

    u = float(jnp.finfo(cfg.compute_dtype).eps) / 2
    return 4 * math.sqrt(2 * cfg.num_layers) * u


def rel_rms(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def make_prompts(vocab: int, seed: int, n: int = N_REQUESTS,
                 lens: tuple = PROMPT_LENS, shared: int = SHARED_PREFIX,
                 page_size: int = SERVE["page_size"]) -> list[list[int]]:
    """``n`` seeded prompts with lengths in ``lens``; prompts 0 and 1 share
    their first ``shared`` tokens.  Prompt 0 fills the page the shared part
    ends in, so prompt 1 finds that page in the radix cache."""
    import numpy as np

    rng = np.random.RandomState(seed)
    sizes = rng.randint(lens[0], lens[1] + 1, n)
    sizes[0] = max(sizes[0], -(-shared // page_size) * page_size)
    sizes[1] = max(sizes[1], shared + 1)
    prompts = [rng.randint(1, vocab, s).tolist() for s in sizes]
    prompts[1][:shared] = prompts[0][:shared]
    return prompts


def require_tpu():
    """The device list, after printing it; exits non-zero without a TPU."""
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d.platform}")
    return devices


def tick_hlo(eng) -> dict[str, str]:
    """Compiled HLO text of the engine's mixed step and decode chunk,
    lowered with the argument shapes its ticks pass."""
    import jax.numpy as jnp
    import numpy as np

    r, s = eng.runner, eng.sched
    C = eng.chunk_tokens
    dec = (jnp.asarray(s.pages), jnp.asarray(s.cur), jnp.asarray(s.pos),
           jnp.asarray(s.remaining), jnp.asarray(s.temp),
           jnp.asarray(s.keys), jnp.asarray(np.zeros(eng.max_batch, bool)))
    mixed = r.mixed_fn(C, eng.max_prefill_variants).lower(
        r.params, r.caches, jnp.zeros((1, C), jnp.int32),
        jnp.asarray(s.pages[:1]), jnp.int32(0), jnp.int32(1),
        jnp.float32(0.0), jnp.asarray(s.keys[0]), jnp.asarray(False), *dec)
    decode = r.decode_fn.lower(r.params, r.caches, *dec)
    return {"mixed_step": mixed.compile().as_text(),
            "decode_chunk": decode.compile().as_text()}


def check_kernels(hlo: dict[str, str]) -> None:
    """Raise unless every executable calls compiled Pallas kernels."""
    for name, text in hlo.items():
        n = text.count("tpu_custom_call")
        print(f"{name}: {n} tpu_custom_call ops", flush=True)
        if not n:
            raise AssertionError(f"{name} holds no tpu_custom_call: the "
                                 f"Pallas kernels are not in the program")


def serve(eng, prompts, max_new: int = MAX_NEW):
    """Serve ``prompts`` greedily to completion; check every request and
    the pool's reconciliation.  Returns (results by rid, wall seconds)."""
    from repro.serving import FinishReason

    t0 = time.time()
    for i, p in enumerate(prompts):
        eng.submit(p, max_new=max_new, temperature=0.0, seed=i)
    results = {r.rid: r for r in eng.run()}
    wall = time.time() - t0
    drained = eng.close()  # asserts the page pool reconciles
    if drained or len(results) != len(prompts):
        raise AssertionError(f"{len(results)} results for {len(prompts)} "
                             f"requests, {len(drained)} left at close")
    done = (FinishReason.STOP, FinishReason.LENGTH)
    bad = {rid: r.finish_reason for rid, r in results.items()
           if r.finish_reason not in done}
    if bad:
        raise AssertionError(f"requests did not finish cleanly: {bad}")
    return results, wall


def paged_logits(cfg, params, prompt, forced, *, page_size: int,
                 chunk: int, mesh=None):
    """``[1 + len(forced), vocab]`` f32 logits: the last prompt row after a
    chunked prefill through the page pool, then one per teacher-forced
    paged decode step.  ``mesh`` places params and pools as the engine does
    and traces under it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from repro.launch.sharding import activation_mesh, tree_pspecs
    from repro.models import model as M

    P, F = len(prompt), len(forced)
    npp = -(-(P + F) // page_size)
    specs = M.paged_cache_specs(cfg, 1, npp + 1, page_size)
    caches = M.init_paged_cache(cfg, 1, npp + 1, page_size)
    if mesh is not None:
        params = M.shard_params(cfg, params, mesh)
        caches = jax.device_put(caches, tree_pspecs(specs, mesh))
    pages = jnp.arange(1, npp + 1, dtype=jnp.int32)[None]
    buf = np.zeros(-(-P // chunk) * chunk, np.int32)
    buf[:P] = prompt

    def run(params, caches, buf, forced):
        for c0 in range(0, P, chunk):
            lg, caches = M.chunk_step(cfg, params, caches,
                                      buf[None, c0: c0 + chunk], pages, c0,
                                      min(chunk, P - c0))

        def step(caches, xs):
            tok, pos = xs
            out, caches = M.decode_step(cfg, params, caches, tok[None, None],
                                        pos[None], pages=pages)
            return caches, out[0, 0]

        pos = P + jnp.arange(F, dtype=jnp.int32)
        _, lgs = lax.scan(step, caches, (forced, pos))
        out = jnp.concatenate([lg[0], lgs])
        return out[:, : cfg.vocab_size].astype(jnp.float32)

    with activation_mesh(mesh) if mesh is not None else nullcontext():
        return np.asarray(jax.jit(run)(params, caches, jnp.asarray(buf),
                                       jnp.asarray(forced, jnp.int32)))


def compare_logits(name: str, got, want, tol: float) -> float:
    import numpy as np

    err = rel_rms(got, want)
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    print(f"logits {name}: rel_rms={err!r} tol={tol!r} "
          f"argmax_agreement={agree!r} (not a criterion)", flush=True)
    if not err <= tol:
        raise AssertionError(f"logits {name}: rel_rms {err} > {tol}")
    return err


def check_logits(cfg, params, prompt, forced, *, page_size: int,
                 chunk: int) -> dict[str, float]:
    """Pallas against the bf16 reference and the float32 reference."""
    import jax
    import jax.numpy as jnp

    kw = dict(page_size=page_size, chunk=chunk)
    mode = cfg.kernel_mode
    got = paged_logits(cfg, params, prompt, forced, **kw)
    ref = paged_logits(cfg.with_(kernel_mode="reference"), params, prompt,
                       forced, **kw)
    f32 = jnp.float32
    cfg32 = cfg.with_(kernel_mode="reference", compute_dtype=f32,
                      param_dtype=f32)
    params32 = jax.tree.map(lambda a: a.astype(f32), params)
    with jax.default_matmul_precision("highest"):
        ref32 = paged_logits(cfg32, params32, prompt, forced, **kw)
    tol = logit_tol(cfg)
    return {"reference": compare_logits(f"{mode} vs reference", got, ref,
                                        tol),
            "f32": compare_logits(f"{mode} vs f32 highest", got, ref32, tol)}


def peak_hbm_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30!r} GiB"


def one_chip(cfg, params, prompts, seed: int, *, mode: str = "pallas",
             serve_kw: dict = SERVE, max_new: int = MAX_NEW) -> None:
    """Serve ``prompts``, check the compiled kernels (``mode="pallas"``)
    and the logits.  ``mode="interpret"`` runs the same phases on a CPU."""
    import numpy as np

    from repro.serving import Engine, EngineConfig

    eng = Engine(cfg, params, EngineConfig(kernel_mode=mode, **serve_kw))
    t0 = time.time()
    hlo = tick_hlo(eng)  # compiles the two executables the ticks reuse
    print(f"compile_s (mixed step + decode chunk): {time.time() - t0!r}",
          flush=True)
    if mode == "pallas":
        check_kernels(hlo)
    results, wall = serve(eng, prompts, max_new)
    tokens = sum(len(r.generated) for r in results.values())
    hit = eng.stats.prefix_hit_tokens
    print(f"served {len(results)} requests, {tokens} tokens in wall_s="
          f"{wall!r} tokens_per_s={tokens / wall!r} prefix_hit_tokens={hit} "
          f"(one smoke run, not a benchmark)", flush=True)
    if not hit:
        raise AssertionError("the shared prefix never hit the radix cache")
    del eng, results

    rng = np.random.RandomState(seed + 1)
    forced = rng.randint(1, cfg.vocab_size, N_FORCED).tolist()
    check_logits(cfg.with_(kernel_mode=mode), params, prompts[0], forced,
                 page_size=serve_kw["page_size"],
                 chunk=serve_kw["chunk_tokens"])


def four_chips(cfg, params, prompts, seed: int, *, mode: str = "pallas",
               serve_kw: dict = SERVE, max_new: int = MAX_NEW) -> None:
    """Serve ``prompts`` on a 1x4 mesh and on device 0 alone, then compare
    the two placements' logits."""
    import jax
    import numpy as np

    from repro.serving import Engine, EngineConfig, MeshSpec

    if jax.device_count() < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX has "
                             f"{jax.device_count()}")
    mesh_spec = MeshSpec(1, 4)
    out = {}
    for name, mesh in (("mesh 1x4", mesh_spec), ("device 0", None)):
        eng = Engine(cfg, params, EngineConfig(kernel_mode=mode, mesh=mesh,
                                               **serve_kw))
        results, wall = serve(eng, prompts, max_new)
        out[name] = [results[i].generated for i in sorted(results)]
        tokens = sum(map(len, out[name]))
        print(f"{name}: served {len(results)} requests, {tokens} tokens in "
              f"wall_s={wall!r} (compile included; one smoke run)",
              flush=True)
        del eng
    same = float(np.mean([a == b for a, b in zip(*out.values())]))
    print(f"greedy outputs identical for {same!r} of the requests "
          f"(not a criterion)", flush=True)

    rng = np.random.RandomState(seed + 1)
    forced = rng.randint(1, cfg.vocab_size, N_FORCED).tolist()
    kw = dict(page_size=serve_kw["page_size"], chunk=serve_kw["chunk_tokens"])
    mcfg = cfg.with_(kernel_mode=mode)
    sharded = paged_logits(mcfg, params, prompts[0], forced,
                           mesh=mesh_spec.build(), **kw)
    single = paged_logits(mcfg, params, prompts[0], forced, **kw)
    compare_logits("mesh 1x4 vs device 0", sharded, single, logit_tol(cfg))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve mesh 1x4 against device 0 alone, only")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: no src/repro beside {Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))

    devices = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    params = M.init(cfg, jax.random.PRNGKey(args.seed))
    prompts = make_prompts(cfg.vocab_size, args.seed)
    print(f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, vocab "
          f"{cfg.vocab_size}, prompt lengths {[len(p) for p in prompts]}",
          flush=True)
    (four_chips if args.chips == 4 else one_chip)(cfg, params, prompts,
                                                   args.seed)
    print(f"peak HBM over the run (device 0): {peak_hbm_gib(devices[0])}",
          flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
