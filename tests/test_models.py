"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + no NaNs, plus prefill -> paged decode
consistency against the full forward pass (catches KV-cache/RoPE/window
bugs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _oracle import paged_prefill

from repro.configs import REGISTRY, get_config, reduce_config
from repro.models import model as M

ARCHS = list(REGISTRY)


def make_batch(cfg, B=2, S=64, rng=None):
    rng = rng or jax.random.PRNGKey(0)
    batch = {}
    if cfg.audio_frontend:
        batch["frames"] = jax.random.normal(rng, (B, S, cfg.frontend_dim), jnp.float32)
    batch["tokens"] = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch["labels"] = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    if cfg.vision_tokens:
        batch["images"] = jax.random.normal(rng, (B, cfg.vision_tokens, cfg.vision_dim))
    return batch


@pytest.fixture(scope="module")
def built():
    out = {}
    for name in ARCHS:
        cfg = reduce_config(get_config(name))
        if cfg.num_experts:
            # full capacity: token drops are load-dependent, so prefill vs
            # decode consistency only holds when nothing is dropped
            cfg = cfg.with_(capacity_factor=float(cfg.num_experts))
        params = M.init(cfg, jax.random.PRNGKey(0))
        out[name] = (cfg, params)
    return out


@pytest.mark.parametrize("name", ARCHS)
def test_forward_loss_finite(built, name):
    cfg, params = built[name]
    batch = make_batch(cfg)
    loss, metrics = M.loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss)), (name, loss)
    assert float(loss) > 0
    hidden, aux, _ = M.forward_hidden(cfg, params, batch, mode="train")
    assert hidden.shape == (2, 64, cfg.d_model)
    assert np.isfinite(np.asarray(hidden, np.float32)).all()


@pytest.mark.parametrize("name", ARCHS)
def test_grad_step_changes_params_finitely(built, name):
    cfg, params = built[name]
    batch = make_batch(cfg)
    grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat), name
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32)))) for g in flat)
    assert gnorm > 0, f"{name}: zero gradient"


@pytest.mark.parametrize("name", [n for n in ARCHS
                                  if REGISTRY[n].kind == "decoder"])
def test_prefill_decode_matches_forward(built, name):
    """logits(prefill(x[:-1]) -> decode(x[-1])) == logits(forward(x))[-1]."""
    cfg, params = built[name]
    B, S = 2, 64
    batch = make_batch(cfg, B, S)
    # full forward logits at the last position
    hidden, _, _ = M.forward_hidden(cfg, params, batch, mode="prefill")
    full_logits = M.lm_logits(cfg, params, hidden[:, -1:])

    # prefill on S-1 tokens, then decode token S-1
    b2 = dict(batch)
    b2["tokens"] = batch["tokens"][:, : S - 1]
    _, caches, pages = paged_prefill(cfg, params, b2, S)
    step_logits, _ = M.decode_step(cfg, params, caches,
                                   batch["tokens"][:, S - 1:],
                                   jnp.int32(S - 1), pages=pages)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0], np.float32),
        np.asarray(full_logits[:, 0], np.float32), atol=2e-2, rtol=1e-2)


@pytest.mark.parametrize("name", [n for n in ARCHS
                                  if REGISTRY[n].kind == "decoder"])
def test_multi_step_decode_consistency(built, name):
    """Decoding tokens one by one reproduces teacher-forced full logits."""
    cfg, params = built[name]
    B, S, extra = 1, 48, 4
    batch = make_batch(cfg, B, S + extra)
    hidden, _, _ = M.forward_hidden(cfg, params, batch, mode="prefill")
    want = M.lm_logits(cfg, params, hidden)  # [B, S+extra, V]

    b2 = dict(batch)
    b2["tokens"] = batch["tokens"][:, :S]
    logits, caches, pages = paged_prefill(cfg, params, b2, S + extra)
    np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                               np.asarray(want[:, S - 1], np.float32),
                               atol=2e-2, rtol=1e-2)
    for i in range(extra):
        logits, caches = M.decode_step(
            cfg, params, caches, batch["tokens"][:, S + i: S + i + 1],
            jnp.int32(S + i), pages=pages)
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   np.asarray(want[:, S + i], np.float32),
                                   atol=3e-2, rtol=1e-2, err_msg=f"{name} step {i}")


def test_sliding_window_decode_far_past_window():
    """Decode far past the window: the window layers' validity bound
    ``start = pos - window + 1`` must track the full forward's window mask
    across page boundaries."""
    cfg = reduce_config(get_config("gemma3-4b"))  # window = 32
    params = M.init(cfg, jax.random.PRNGKey(0))
    B, S, extra = 1, 60, 8  # prefill spans nearly 2 windows
    batch = make_batch(cfg, B, S + extra)
    hidden, _, _ = M.forward_hidden(cfg, params, batch, mode="prefill")
    want = M.lm_logits(cfg, params, hidden)
    b2 = {"tokens": batch["tokens"][:, :S]}
    logits, caches, pages = paged_prefill(cfg, params, b2, S + extra)
    for i in range(extra):
        logits, caches = M.decode_step(cfg, params, caches,
                                       batch["tokens"][:, S + i: S + i + 1],
                                       jnp.int32(S + i), pages=pages)
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   np.asarray(want[:, S + i], np.float32),
                                   atol=3e-2, rtol=1e-2, err_msg=f"step {i}")


@pytest.mark.parametrize("name", ARCHS)
def test_param_count_full_config_sane(name):
    """Full (non-reduced) configs hit their advertised parameter class."""
    from repro.launch.roofline import active_params
    cfg = get_config(name)
    total, active = active_params(cfg)
    expected = {
        "gemma3-4b": (3e9, 6e9), "minicpm3-4b": (3e9, 6e9),
        "olmo-1b": (0.9e9, 2e9), "deepseek-67b": (60e9, 72e9),
        "jamba-v0.1-52b": (45e9, 60e9), "kimi-k2-1t-a32b": (0.9e12, 1.2e12),
        "qwen3-moe-30b-a3b": (28e9, 34e9), "mamba2-130m": (0.1e9, 0.2e9),
        "llama-3.2-vision-11b": (9e9, 13e9), "hubert-xlarge": (0.8e9, 1.3e9),
        "cgra-edge": (1e6, 3e8),
    }[name]
    assert expected[0] <= total <= expected[1], (name, total)
    assert active <= total


def test_unrolled_matches_scanned():
    """scan_layers=False (cost-compile path) is numerically identical."""
    cfg = reduce_config(get_config("deepseek-67b"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    l1, _ = M.loss_fn(cfg, params, batch)
    l2, _ = M.loss_fn(cfg.with_(scan_layers=False), params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_attn_chunking_matches_unchunked():
    cfg = reduce_config(get_config("olmo-1b"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, B=2, S=64)
    l1, _ = M.loss_fn(cfg, params, batch)
    l2, _ = M.loss_fn(cfg, params, batch, attn_chunk=16)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
