"""Paged continuous-batching engine: page allocation/recycling, radix prefix
reuse (hit accounting, COW divergence, capacity wins at fixed memory),
greedy parity with the single-sequence paged oracle (linear and
sliding-window configs, reference and interpret kernel modes), admission
control, the legacy-kwargs deprecation shim, and mid-flight arrivals."""
import warnings

import jax
import numpy as np
import pytest
from _oracle import reference_greedy

from repro.configs import get_config, reduce_config
from repro.models import model as M
from repro.serving import (Engine, EngineConfig, FinishReason,
                           bytes_tokenizer_encode)


@pytest.fixture(scope="module")
def olmo():
    cfg = reduce_config(get_config("olmo-1b"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def gemma():
    """Local/global interleave with a sliding window — the window layers
    express validity via ``start = pos - window + 1``."""
    cfg = reduce_config(get_config("gemma3-4b"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def edge():
    cfg = reduce_config(get_config("cgra-edge"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, texts):
    return [bytes_tokenizer_encode(t, cfg.vocab_size) for t in texts]


def _econ(**kw):
    kw.setdefault("max_len", 96)
    kw.setdefault("page_size", 16)
    kw.setdefault("decode_chunk", 4)
    return EngineConfig(**kw)


# ---------------------------------------------------------------------------
# scheduling / page lifecycle
# ---------------------------------------------------------------------------

def test_page_recycling_and_reuse(olmo):
    """5 requests through 2 batch rows: pages recycle through the pool and
    every request completes with its full token budget; after the drain the
    pool holds only radix-cached pages."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2))
    prompts = _prompts(cfg, ["a", "bb", "ccc", "dddd", "eeeee"])
    rids = [eng.submit(p, max_new=5) for p in prompts]
    results = {r.rid: r for r in eng.run()}
    assert sorted(results) == sorted(rids)
    for rid, p in zip(rids, prompts):
        assert len(results[rid].generated) == 5
        assert results[rid].prompt == p
    assert eng.num_active == 0 and eng.num_queued == 0
    assert eng.stats.prefills == 5
    assert eng.stats.peak_active <= 2
    # every page either returned to the free list or is held by the tree
    for pid in range(1, eng.pool.n_pages):
        assert eng.pool.refcount(pid) in (0, 1)


def test_matches_unbatched_reference_greedy(olmo):
    """Batched paged scan decode == the single-sequence paged oracle, token
    for token."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=3))
    prompts = _prompts(cfg, ["hello world", "x", "the quick brown fox"])
    out, _ = eng.generate(prompts, max_new=6)
    for p, seq in zip(prompts, out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 6)


def test_window_config_matches_reference(gemma):
    """Sliding-window layers on the paged cache (validity via start) decode
    identically in the engine and in the single-sequence oracle, for prompts
    shorter AND longer than the window."""
    cfg, params = gemma
    assert cfg.window_size and cfg.local_global_pattern
    eng = Engine(cfg, params, _econ(max_len=128, max_batch=2))
    short = _prompts(cfg, ["tiny"])[0]                      # < window
    long = _prompts(cfg, ["w" * (cfg.window_size + 9)])[0]  # > window
    out, _ = eng.generate([short, long], max_new=6)
    for p, seq in zip([short, long], out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 6)


def test_greedy_independent_of_batch_composition(olmo):
    cfg, params = olmo
    target = _prompts(cfg, ["the target request"])[0]
    mates_a = _prompts(cfg, ["one", "completely different"])
    mates_b = _prompts(cfg, ["nine nine nine nine nine nine"])

    def gen_with(mates, max_batch):
        eng = Engine(cfg, params, _econ(max_batch=max_batch))
        out, _ = eng.generate([target] + mates, max_new=6)
        return out[0]

    solo = gen_with([], 1)
    assert gen_with(mates_a, 3) == solo
    assert gen_with(mates_b, 2) == solo


def test_admission_control(olmo):
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_len=64, max_batch=1, max_queue=2))
    with pytest.raises(ValueError):  # can never fit: 40 + 32 > max_len
        eng.submit(list(range(40)), max_new=32)
    with pytest.raises(ValueError):
        eng.submit([], max_new=4)
    eng.submit([1, 2, 3], max_new=4)
    eng.submit([1, 2, 3], max_new=4)
    # queue bound -> backpressure: never a raise or a silent drop, the
    # request finishes immediately as REJECTED with a retry hint
    rej = eng.submit([1, 2, 3], max_new=4)
    res = {r.rid: r for r in eng.run()}
    assert len(res) == 3
    assert res[rej].finish_reason == FinishReason.REJECTED
    assert not res[rej].ok and res[rej].retry_after_s > 0
    assert eng.stats.rejected == 1
    assert sum(r.ok for r in res.values()) == 2


def test_admission_rejects_requests_larger_than_pool(olmo):
    """A request whose page need exceeds the whole pool can never run."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_len=96, max_batch=1, n_pages=3))
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(40)), max_new=8)  # needs 3 pages, pool has 2


def test_head_of_line_blocking_until_pages_free(olmo):
    """When the pool cannot serve the head request, admission waits for
    retirements instead of failing — and the request then completes."""
    cfg, params = olmo
    # 5 usable pages: an in-flight 3-page request leaves 2 free; the queued
    # 3-page request must wait for the first to retire.
    eng = Engine(cfg, params, _econ(max_len=96, max_batch=2, n_pages=6,
                                    prefix_cache=False))
    a = eng.submit(list(range(40)), max_new=8)      # 3 pages
    b = eng.submit(list(range(40, 80)), max_new=8)  # 3 pages: must wait
    results = eng.step()
    assert eng.num_active == 1 and eng.num_queued == 1
    while eng.num_active or eng.num_queued:
        results.extend(eng.step())
    assert sorted(r.rid for r in results) == [a, b]
    assert all(len(r.generated) == 8 for r in results)


def test_mid_flight_arrival(olmo):
    """Requests submitted while others decode land in freed batch rows and
    finish with results identical to a solo run (continuous batching)."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2, decode_chunk=2))
    first = _prompts(cfg, ["alpha", "beta"])
    late = _prompts(cfg, ["late arrival"])[0]
    for p in first:
        eng.submit(p, max_new=8)
    results = list(eng.step())  # decode in flight
    eng.submit(late, max_new=4)
    while eng.num_active or eng.num_queued:
        results.extend(eng.step())
    by_rid = {r.rid: r for r in results}
    assert len(by_rid) == 3
    solo = Engine(cfg, params, _econ(max_batch=2, decode_chunk=2))
    solo_out, _ = solo.generate([late], max_new=4)
    assert by_rid[2].tokens == solo_out[0]


def test_eos_stops_early(olmo):
    cfg, params = olmo
    probe = Engine(cfg, params, _econ(max_batch=1))
    p = _prompts(cfg, ["stop early"])[0]
    out, _ = probe.generate([p], max_new=8)
    gen = out[0][len(p):]
    eos = gen[2]  # pretend the 3rd generated token is the stop token
    eng = Engine(cfg, params, _econ(max_batch=1, eos_id=eos))
    res = {r.rid: r for r in (eng.submit(p, max_new=8), eng.run())[1]}
    assert res[0].generated == gen[: gen.index(eos) + 1]  # cut at first eos
    assert res[0].generated[-1] == eos


def test_per_request_temperature_and_seed(olmo):
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2))
    p = _prompts(cfg, ["sample me"])[0]
    r1 = eng.submit(p, max_new=10, temperature=1.0, seed=1)
    r2 = eng.submit(p, max_new=10, temperature=1.0, seed=2)
    res = {r.rid: r for r in eng.run()}
    assert res[r1].generated != res[r2].generated


def test_decode_past_capacity_is_explicit_error(olmo):
    """A slot whose length accounting would overrun its reserved pages must
    surface an explicit error, never silently write the trash page."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_len=32, max_batch=1))
    eng.submit(_prompts(cfg, ["overrun"])[0], max_new=8)  # legal: 15 <= 32
    eng.step()
    assert eng.num_active == 1
    eng._remaining[0] = 1000  # simulate corrupted length accounting
    with pytest.raises(RuntimeError, match="overruns KV capacity"):
        while eng.num_active:
            eng.step()


# ---------------------------------------------------------------------------
# prefix reuse
# ---------------------------------------------------------------------------

def test_prefix_hit_shares_pages_and_outputs_match(olmo):
    """Two requests sharing a 40-token prefix (2.5 pages of 16): the second
    admission incref-shares the 2 full pages, takes the third (where the
    prompts diverge at row 8) as a copy-on-write share, and still emits
    exactly the solo-run tokens."""
    cfg, params = olmo
    rng = np.random.RandomState(0)
    prefix = rng.randint(3, cfg.vocab_size, 40).tolist()
    p1 = prefix + [1] * 8  # 48 tokens: exactly 3 full pages
    p2 = prefix + [2] * 6  # diverges from p1 at row 8 of page 3
    eng = Engine(cfg, params, _econ(max_batch=2))
    out, stats = eng.generate([p1, p2], max_new=6)
    # p2 matched 2 full pages (32) + 8 COW rows of p1's cached third page
    assert stats.prefix_hit_tokens == 40
    assert stats.prefix_lookup_tokens == len(p1) + len(p2)
    assert eng.prefix_hit_rate == pytest.approx(40 / 94)
    for p, seq in zip([p1, p2], out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 6)


def test_matched_prefix_pages_survive_eviction_pressure(olmo):
    """Regression: the pages a radix lookup matches must be pinned before
    eviction runs.  Unpinned, a tree-only matched page (refcount 1) was a
    legitimate LRU victim for the very evict() making room for the same
    request — incref then hit a freed page (crash), or worse the page was
    handed to another sequence.  Now the blocked request simply waits."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_len=96, max_batch=2, n_pages=7))
    rng = np.random.RandomState(7)
    prefix = rng.randint(3, cfg.vocab_size, 32).tolist()
    a = eng.submit(prefix, max_new=16)                # 3 pages
    results = eng.run()                               # retires; 2 prompt
    assert eng.pool.num_free == 4                     # pages stay cached
    filler = rng.randint(3, cfg.vocab_size, 16).tolist()
    c = eng.submit(filler, max_new=32)                # 3 pages: pool drained
    results.extend(eng.step())
    assert eng.num_active == 1 and eng.pool.num_free == 1
    pb = prefix + rng.randint(3, cfg.vocab_size, 16).tolist()
    b = eng.submit(pb, max_new=16)                    # 4 pages, 2 matched
    results.extend(eng.step())
    assert eng.num_queued == 1                        # blocked, not crashed
    assert eng.pool.refcount(1) == 1                  # matched prefix pages
    assert eng.pool.refcount(2) == 1                  # still radix-held
    while eng.num_active or eng.num_queued:
        results.extend(eng.step())
    by_rid = {r.rid: r for r in results}
    assert sorted(by_rid) == sorted([a, b, c])
    assert by_rid[b].generated == reference_greedy(cfg, params, pb, 16)
    for pid in range(1, eng.pool.n_pages):            # seq refs all released
        assert eng.pool.refcount(pid) in (0, 1)


def test_blocked_admission_does_not_evict_prefix_cache(olmo):
    """Regression: when the head-of-line request stays blocked even after
    eviction could run, admission must not evict at all — cached prefix
    pages were being thrown away for a request that remained queued."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_len=96, max_batch=2, n_pages=7))
    rng = np.random.RandomState(8)
    prefix = rng.randint(3, cfg.vocab_size, 32).tolist()
    eng.submit(prefix, max_new=16)                    # 3 pages
    results = eng.run()                               # tree keeps 2 pages
    eng.submit(rng.randint(3, cfg.vocab_size, 16).tolist(), max_new=32)
    results.extend(eng.step())                        # live: 3 pages
    assert eng.pool.num_free == 1
    pb = rng.randint(3, cfg.vocab_size, 32).tolist()  # no shared prefix
    b = eng.submit(pb, max_new=32)                    # 4 pages, 0 matched
    results.extend(eng.step())
    assert eng.num_queued == 1                        # blocked: 1 free + 2
    assert eng.pool.refcount(1) == 1                  # evictable < 4 needed,
    assert eng.pool.refcount(2) == 1                  # so nothing evicted
    while eng.num_active or eng.num_queued:           # retirement frees 2;
        results.extend(eng.step())                    # now eviction helps
    by_rid = {r.rid: r for r in results}
    assert by_rid[b].generated == reference_greedy(cfg, params, pb, 32)


def test_prefill_compile_cache_is_bounded(olmo):
    """The suffix-prefill jit cache LRU-evicts beyond max_prefill_variants
    (unbounded growth under varied prompt lengths), and recompiling an
    evicted variant stays correct."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=1))
    eng.max_prefill_variants = 2
    prompts = _prompts(cfg, ["a", "bb", "ccc", "dddd", "eee"])
    out, _ = eng.generate(prompts, max_new=4)
    assert len(eng._prefill_fns) <= 2
    for p, seq in zip(prompts, out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 4)


def test_prefix_cache_auto_disabled_for_ssm():
    """SSM prefill is not prefix-decomposable: the engine must refuse to
    radix-share even when the config asks for it."""
    cfg = reduce_config(get_config("mamba2-130m"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, _econ(max_batch=2, prefix_cache=True))
    assert eng.radix is None
    p = _prompts(cfg, ["state space"])[0]
    out, _ = eng.generate([p], max_new=4)
    assert len(out[0]) == len(p) + 4


def test_paged_prefix_reuse_beats_fixed_slot_at_equal_memory(edge):
    """ISSUE acceptance: 8 requests sharing a 512-token prefix.  At an
    equal KV row budget the paged+radix engine decodes all 8 concurrently
    where fixed per-slot allocation fits a single sequence — and every
    output stays bit-identical to the single-sequence oracle."""
    cfg, params = edge
    ps, n_req, prefix_len, suffix_len, max_new = 64, 8, 512, 8, 8
    rng = np.random.RandomState(3)
    prefix = rng.randint(1, cfg.vocab_size, prefix_len).tolist()
    prompts = [prefix + rng.randint(1, cfg.vocab_size, suffix_len).tolist()
               for _ in range(n_req)]
    rows = prefix_len + suffix_len + max_new  # 528 rows per request
    max_len = -(-rows // ps) * ps             # 576
    # budget: the shared prefix once + one private tail page per request
    n_pages = 1 + prefix_len // ps + n_req * (
        -(-rows // ps) - prefix_len // ps)
    econ = EngineConfig(max_len=max_len, max_batch=n_req, page_size=ps,
                        n_pages=n_pages, decode_chunk=4)
    eng = Engine(cfg, params, econ)
    out, stats = eng.generate(prompts, max_new=max_new)
    # all 8 admitted at once: the prefix pages are shared, not copied ...
    assert stats.peak_active == n_req
    # ... which is strictly more than per-slot allocation at equal memory
    fixed_slot_concurrency = econ.cache_spec().max_rows // max_len
    assert stats.peak_active > fixed_slot_concurrency
    assert fixed_slot_concurrency == 1
    assert eng.prefix_hit_rate > 0.5  # requests 2..8 each hit 512/520
    # engine-vs-oracle greedy outputs: bit-identical
    for p, seq in zip(prompts, out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, max_new)


# ---------------------------------------------------------------------------
# EngineConfig surface / legacy shim
# ---------------------------------------------------------------------------

def test_legacy_kwargs_shim(olmo):
    """The pre-paging Engine signature still works, under DeprecationWarning:
    max_slots -> max_batch, prefill_bucket ignored, capacity preserved."""
    cfg, params = olmo
    with pytest.warns(DeprecationWarning):
        eng = Engine(cfg, params, max_len=96, max_slots=2, prefill_bucket=16,
                     decode_chunk=4)
    assert eng.max_batch == 2 and eng.decode_chunk == 4
    assert eng.cache_spec.max_rows >= 2 * 96  # legacy row capacity kept
    with pytest.warns(DeprecationWarning):  # legacy positional max_len
        eng2 = Engine(cfg, params, 96)
    assert eng2.max_len >= 96
    p = _prompts(cfg, ["legacy caller"])[0]
    out, _ = eng.generate([p], max_new=5)
    assert len(out[0]) == len(p) + 5


def test_engine_config_and_legacy_kwargs_are_exclusive(olmo):
    cfg, params = olmo
    with pytest.raises(TypeError):
        Engine(cfg, params, EngineConfig(), max_slots=2)
    with pytest.raises(TypeError):
        Engine(cfg, params, bogus_knob=1)


def test_engine_config_defaults_no_warning(olmo):
    cfg, params = olmo
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        eng = Engine(cfg, params)
        Engine(cfg, params, EngineConfig(max_len=128, page_size=32))
    assert eng.max_len == 512 and eng.page_size == 64
    assert eng.pool.n_pages == 8 * (512 // 64) + 1


def test_engine_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(page_size=12)  # not a multiple of 8
    with pytest.raises(ValueError):
        EngineConfig(n_pages=1)
    assert EngineConfig(max_len=100, page_size=32).max_len == 128  # rounded


# ---------------------------------------------------------------------------
# kernel_mode / quant through the paged engine
# ---------------------------------------------------------------------------

def test_engine_w8a8_serves_full_budget(olmo):
    """quant="w8a8": weights quantized once at engine construction; prefill
    and scan-decode run through the packed int8 GEMM path end to end."""
    from repro.core.quant import QTensor
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2, quant="w8a8"))
    assert eng.cfg.quant == "w8a8"
    assert isinstance(eng.params["lm_head"], QTensor)
    prompts = _prompts(cfg, ["int8 one", "int8 two", "int8 three"])
    out, _ = eng.generate(prompts, max_new=6)
    for p, seq in zip(prompts, out):
        assert len(seq) == len(p) + 6
        assert all(0 <= t < cfg.vocab_size for t in seq)


def test_paged_interpret_matches_single_sequence_on_cgra_edge(edge):
    """Engine-vs-single-sequence greedy parity on the edge config in
    interpret mode — both sides run the exact Pallas kernel math over paged
    pools; the engine side batches, chunks, and shares a prefix (radix
    reuse + partial-page COW), the oracle prefills one whole prompt into an
    identity page table."""
    cfg, params = edge
    cfg_i = cfg.with_(kernel_mode="interpret")
    common = "shared edge prefix tokens: "  # 27 bytes: 1 full 16-page + COW
    prompts = _prompts(cfg, [common + "request one", common + "request two",
                             "cold prompt"])
    eng = Engine(cfg_i, params, _econ(max_len=64, max_batch=2))
    out, _ = eng.generate(prompts, max_new=6)
    assert eng.stats.prefix_hit_tokens > 16  # page share + COW rows hit
    for p, seq in zip(prompts, out):
        assert seq[len(p):] == reference_greedy(cfg_i, params, p, 6)


def test_engine_kernel_mode_override(olmo):
    """kernel_mode is threaded from the config into prefill + decode; the
    reference override must reproduce the default engine token-for-token."""
    cfg, params = olmo
    a = Engine(cfg, params, _econ(max_batch=1))
    b = Engine(cfg, params, _econ(max_batch=1, kernel_mode="reference"))
    p = _prompts(cfg, ["kernel mode"])[0]
    assert a.generate([p], max_new=5)[0] == b.generate([p], max_new=5)[0]
