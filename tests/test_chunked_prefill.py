"""Chunked prefill + the unified mixed step: paged-past prefill attention
parity (oracle vs dense, interpret kernel vs oracle), engine greedy
bit-parity across ``chunk_tokens`` in {8, 32, None} (linear,
sliding-window, and interpret-mode cgra-edge configs), radix prefix hits
landing mid-chunk, decode retirement on the same tick a chunk runs,
``submit`` input validation, and the bounded mixed-step compile cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _oracle import reference_greedy

from repro.configs import get_config, reduce_config
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.models import model as M
from repro.serving import Engine, EngineConfig, bytes_tokenizer_encode


@pytest.fixture(scope="module")
def olmo():
    cfg = reduce_config(get_config("olmo-1b"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def gemma():
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
# kernel: query-chunk attention over a paged past
# ---------------------------------------------------------------------------

def _rand_paged(seed=0, B=2, H=4, K=2, C=16, ps=16, npp=3, d=16):
    """Random page pools with shuffled per-sequence page tables; sequence 0
    starts its chunk mid-stream (a cached past), sequence 1 at position 0."""
    rng = np.random.RandomState(seed)
    P = 1 + B * npp  # page 0 reserved
    q = rng.randn(B, H, C, d).astype(np.float32)
    kp = rng.randn(P, K, ps, d).astype(np.float32)  # head-major pools
    vp = rng.randn(P, K, ps, d).astype(np.float32)
    pages = np.zeros((B, npp), np.int32)
    for b in range(B):
        pages[b] = 1 + b * npp + rng.permutation(npp)
    q_start = np.array([ps + 3, 0], np.int32)[:B]
    k_len = q_start + C
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pages), jnp.asarray(q_start), jnp.asarray(k_len))


@pytest.mark.parametrize("window,softcap",
                         [(0, 0.0), (20, 0.0), (0, 15.0), (12, 9.0)])
def test_paged_prefill_oracle_matches_dense(window, softcap):
    """The paged-past oracle == per-sequence dense suffix-causal attention
    on the gathered pages (the alignment the engine's chunks rely on)."""
    q, kp, vp, pages, q_start, k_len = _rand_paged()
    out = ref.flash_attention_paged_ref(q, kp, vp, pages, q_start, k_len,
                                        window=window, softcap=softcap)
    B, H, C, d = q.shape
    G = H // kp.shape[1]
    for b in range(B):
        # [npp, K, ps, d] -> [K, npp * ps, d]: the sequence's rows per head
        kd = kp[pages[b]].transpose(1, 0, 2, 3).reshape(kp.shape[1], -1, d)
        vd = vp[pages[b]].transpose(1, 0, 2, 3).reshape(vp.shape[1], -1, d)
        kb = jnp.repeat(kd[:, : int(k_len[b])], G, axis=0)[None]
        vb = jnp.repeat(vd[:, : int(k_len[b])], G, axis=0)[None]
        dense = ref.flash_attention_ref(q[b: b + 1], kb, vb, causal=True,
                                        window=window, softcap=softcap)
        np.testing.assert_allclose(out[b], dense[0], atol=2e-5, rtol=2e-5)


def _stack_layers(pool, layer, n=3, seed=0):
    """``pool`` as layer ``layer`` of ``n`` stacked pools whose other layers
    hold other values: a read that ignores the layer index reads those."""
    rng = np.random.RandomState(seed)
    layers = [jnp.asarray(rng.randn(*pool.shape), pool.dtype)
              for _ in range(n)]
    layers[layer] = pool
    return jnp.stack(layers)


@pytest.mark.parametrize("layer", [None, 0, 2])
@pytest.mark.parametrize("window,softcap",
                         [(0, 0.0), (20, 0.0), (0, 15.0), (12, 9.0)])
def test_paged_prefill_kernel_matches_oracle(window, softcap, layer):
    """Interpret-mode Pallas kernel (scalar-prefetch page-table index map,
    dead-block DMA elision) == the jnp oracle.  ``layer``: the pools are
    read as layer 0 or 2 of a stack of three whose other layers hold other
    values (``None``: one layer's bare pools)."""
    q, kp, vp, pages, q_start, k_len = _rand_paged(seed=1)
    want = ref.flash_attention_paged_ref(q, kp, vp, pages, q_start, k_len,
                                         window=window, softcap=softcap)
    if layer is not None:
        kp, vp = (_stack_layers(kp, layer, seed=1),
                  _stack_layers(vp, layer, seed=2))
    got = flash_attention(q, kp, vp, pages=pages, q_start=q_start,
                          k_len=k_len, layer=layer, window=window,
                          softcap=softcap, interpret=True)
    oracle = ref.flash_attention_paged_ref(q, kp, vp, pages, q_start, k_len,
                                           window=window, softcap=softcap,
                                           layer=layer)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(oracle, want, atol=2e-5, rtol=2e-5)


def test_paged_prefill_kernel_shared_kv_and_tail():
    """k is v (MQA-style shared pool) and the chunk is shorter than the
    buffer: the valid rows still match the oracle."""
    q, kp, _, pages, q_start, k_len = _rand_paged(seed=2, B=1, K=1, H=2)
    n = 11  # valid chunk rows; the engine discards the rest
    want = ref.flash_attention_paged_ref(q, kp, kp, pages, q_start,
                                         q_start + n)
    got = flash_attention(q, kp, kp, pages=pages, q_start=q_start,
                          k_len=q_start + n, interpret=True)
    np.testing.assert_allclose(got[:, :, :n], want[:, :, :n],
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# engine: chunked schedules are bit-identical to whole-prompt prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_tokens", [8, 32, None])
def test_chunked_greedy_parity_linear(olmo, chunk_tokens):
    """Every chunk schedule — 8-token chunks, 32-token chunks, whole-suffix
    — produces the same greedy tokens as the whole-prompt oracle."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=3, chunk_tokens=chunk_tokens))
    prompts = _prompts(cfg, ["hello world", "x",
                             "a prompt long enough to span several chunks"])
    out, stats = eng.generate(prompts, max_new=6)
    for p, seq in zip(prompts, out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 6)
    assert stats.prefills == 3


@pytest.mark.parametrize("chunk_tokens", [8, None])
def test_chunked_window_parity(gemma, chunk_tokens):
    """Sliding-window layers: chunks crossing the window boundary attend
    through the paged past with the same masking as whole-prompt prefill."""
    cfg, params = gemma
    assert cfg.window_size and cfg.local_global_pattern
    eng = Engine(cfg, params, _econ(max_len=128, max_batch=2,
                                    chunk_tokens=chunk_tokens))
    short = _prompts(cfg, ["tiny"])[0]                      # < window
    long = _prompts(cfg, ["w" * (cfg.window_size + 9)])[0]  # > window
    out, _ = eng.generate([short, long], max_new=6)
    for p, seq in zip([short, long], out):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 6)


@pytest.mark.parametrize("chunk_tokens", [8, 32, None])
def test_chunked_interpret_parity_edge(edge, chunk_tokens):
    """cgra-edge in interpret mode: the chunked schedule runs the exact
    Pallas kernel math (paged prefill + paged decode), with a shared prefix
    exercising radix reuse inside a chunked prefill."""
    cfg, params = edge
    cfg_i = cfg.with_(kernel_mode="interpret")
    common = "shared edge prefix tokens: "  # 1 full 16-row page + COW tail
    prompts = _prompts(cfg, [common + "request one", common + "request two"])
    eng = Engine(cfg_i, params, _econ(max_len=64, max_batch=2,
                                      chunk_tokens=chunk_tokens))
    out, _ = eng.generate(prompts, max_new=4)
    assert eng.stats.prefix_hit_tokens >= 16
    for p, seq in zip(prompts, out):
        assert seq[len(p):] == reference_greedy(cfg_i, params, p, 4)


def test_radix_hit_lands_mid_chunk(olmo):
    """A prefix hit that is not chunk-aligned: the follow-up request starts
    prefilling at the matched offset (16 or 24 tokens into a 32-token chunk
    budget) and still matches the oracle token for token."""
    cfg, params = olmo
    rng = np.random.RandomState(7)
    base = rng.randint(1, cfg.vocab_size, 20).tolist()
    follow_full = base[:16] + rng.randint(1, cfg.vocab_size, 9).tolist()
    follow_cow = base[:10] + rng.randint(1, cfg.vocab_size, 7).tolist()
    eng = Engine(cfg, params, _econ(max_batch=2, chunk_tokens=32))
    out, _ = eng.generate([base], max_new=4)          # publishes 1 full page
    out2, _ = eng.generate([follow_full, follow_cow], max_new=4)
    # follow_full hits the whole published page (prefill starts at row 16);
    # follow_cow diverges mid-page (COW share, prefill starts at row 10)
    assert eng.stats.prefix_hit_tokens >= 16 + 10
    for p, seq in zip([follow_full, follow_cow], out2):
        assert seq[len(p):] == reference_greedy(cfg, params, p, 4)


def test_decode_retires_on_mixed_tick(olmo):
    """A decoding slot that exhausts its budget on a tick that also runs a
    prefill chunk retires that same tick, while the chunked prompt keeps
    prefilling — and both outputs match the oracle."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2, chunk_tokens=8))
    short = _prompts(cfg, ["hi"])[0]
    long = _prompts(cfg, ["a sixty-ish byte prompt padded " + "y" * 30])[0]
    assert len(long) > 3 * 8  # several chunks
    ra = eng.submit(short, max_new=2)
    eng.step()  # short's prefill chunk completes; 1 decode token left
    rb = eng.submit(long, max_new=3)
    mixed = eng.step()  # long's first chunk + short's last decode step
    assert [r.rid for r in mixed] == [ra]
    assert eng.num_active == 1  # long still prefilling
    results = {r.rid: r for r in mixed}
    while eng.num_active or eng.num_queued:
        results.update({r.rid: r for r in eng.step()})
    assert results[ra].generated == reference_greedy(cfg, params, short, 2)
    assert results[rb].generated == reference_greedy(cfg, params, long, 3)


# ---------------------------------------------------------------------------
# submit validation + compile-cache bounds
# ---------------------------------------------------------------------------

def test_submit_validation(olmo):
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=1))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], max_new=4)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=0)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=-3)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=2.5)
    with pytest.raises(ValueError, match="tokens"):
        eng.submit([1, cfg.vocab_size], max_new=4)  # out of vocab
    with pytest.raises(ValueError, match="tokens"):
        eng.submit([1, -1], max_new=4)
    with pytest.raises(ValueError, match="tokens"):
        eng.submit([1, 2.5], max_new=4)  # non-integer token
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], max_new=4, temperature=-0.5)
    assert eng.num_queued == 0  # nothing malformed was admitted
    eng.submit([1, 2], max_new=4)
    assert len(eng.run()) == 1


def test_single_mixed_variant_under_chunking(olmo):
    """With ``chunk_tokens`` set, every prompt length shares ONE compiled
    mixed-step variant — the per-(prefix, suffix) prefill executable cache
    is gone for decomposable models."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2, chunk_tokens=16))
    prompts = [list(range(1, 1 + n)) for n in (3, 17, 30, 41, 55)]
    out, _ = eng.generate(prompts, max_new=3)
    assert all(len(s) == len(p) + 3 for p, s in zip(prompts, out))
    assert set(eng._prefill_fns) == {("mixed", 16)}


def test_bucketed_variants_without_chunking(olmo):
    """Unchunked, whole-suffix chunks compile per power-of-two bucket, not
    per prompt length."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2, prefix_cache=False))
    prompts = [list(range(1, 1 + n)) for n in (3, 5, 17, 30, 41)]
    eng.generate(prompts, max_new=3)
    assert set(eng._prefill_fns) == {("mixed", 8), ("mixed", 32),
                                     ("mixed", 64)}
