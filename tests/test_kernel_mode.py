"""kernel_mode / w8a8 wiring through the model hot path.

With ``kernel_mode="interpret"`` every dense projection runs through the
Pallas block-GEMM and forward/prefill attention through the Pallas flash
kernel (interpreted on CPU — the exact kernel math), so these tests pin the
whole integration: config -> layers.dense_proj / dispatch_attend ->
kernels.ops -> Pallas.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _oracle import paged_prefill

from repro.configs import get_config, reduce_config
from repro.core.quant import QTensor
from repro.models import model as M

ATOL = 1e-4


@pytest.fixture(scope="module")
def edge():
    cfg = get_config("cgra-edge")
    params = M.init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                              cfg.vocab_size)
    return cfg, params, {"tokens": toks}


def test_interpret_forward_matches_reference(edge):
    cfg, params, batch = edge
    h_ref, _, _ = M.forward_hidden(cfg, params, batch, mode="train")
    lg_ref = M.lm_logits(cfg, params, h_ref)
    cfg_i = cfg.with_(kernel_mode="interpret")
    h_i, _, _ = M.forward_hidden(cfg_i, params, batch, mode="train")
    lg_i = M.lm_logits(cfg_i, params, h_i)
    np.testing.assert_allclose(np.asarray(lg_i), np.asarray(lg_ref), atol=ATOL)


def test_interpret_prefill_matches_reference(edge):
    cfg, params, batch = edge
    lg_ref, caches_ref = M.prefill(cfg, params, batch)
    lg_i, caches_i = M.prefill(cfg.with_(kernel_mode="interpret"), params,
                               batch)
    np.testing.assert_allclose(np.asarray(lg_i), np.asarray(lg_ref), atol=ATOL)
    for a, b in zip(jax.tree.leaves(caches_ref), jax.tree.leaves(caches_i)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL)


def test_interpret_ragged_prompt_lengths(edge):
    """Non-block-multiple S must run without divisibility assertions."""
    cfg, params, _ = edge
    cfg_i = cfg.with_(kernel_mode="interpret")
    for S in (7, 33):
        toks = jax.random.randint(jax.random.PRNGKey(S), (1, S), 0,
                                  cfg.vocab_size)
        lg_ref, _ = M.prefill(cfg, params, {"tokens": toks})
        lg_i, _ = M.prefill(cfg_i, params, {"tokens": toks})
        np.testing.assert_allclose(np.asarray(lg_i), np.asarray(lg_ref),
                                   atol=ATOL, err_msg=f"S={S}")


def test_interpret_gemma_window_softcap():
    """Local/global interleave + sliding window + softcap through the flash
    kernel path, vs the reference path."""
    cfg = reduce_config(get_config("gemma3-4b")).with_(logit_softcap=30.0)
    params = M.init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 48), 0,
                              cfg.vocab_size)
    h_ref, _, _ = M.forward_hidden(cfg, params, {"tokens": toks}, mode="train")
    h_i, _, _ = M.forward_hidden(cfg.with_(kernel_mode="interpret"), params,
                                 {"tokens": toks}, mode="train")
    np.testing.assert_allclose(np.asarray(h_i, np.float32),
                               np.asarray(h_ref, np.float32), atol=1e-3)


def test_interpret_decode_matches_reference(edge):
    """Decode hot path obeys kernel_mode: interpret-mode ``decode_step``
    (paged flash-decode Pallas kernel through the interpreter) matches the
    jnp reference to <= 1e-4 logits on the edge config, stepping from the
    same pools (a whole prefill written through an identity page table)."""
    cfg, params, batch = edge
    toks = batch["tokens"]
    plen = toks.shape[1] - 3
    _, caches, pages = paged_prefill(cfg, params, {"tokens": toks[:, :plen]},
                                     toks.shape[1])
    cfg_i = cfg.with_(kernel_mode="interpret")
    for step in range(3):
        lg_ref, caches_ref = M.decode_step(
            cfg, params, caches, toks[:, plen + step: plen + step + 1],
            jnp.int32(plen + step), pages=pages)
        lg_i, caches_i = M.decode_step(
            cfg_i, params, caches, toks[:, plen + step: plen + step + 1],
            jnp.int32(plen + step), pages=pages)
        np.testing.assert_allclose(np.asarray(lg_i), np.asarray(lg_ref),
                                   atol=ATOL, err_msg=f"step {step}")
        caches = caches_ref
    for a, b in zip(jax.tree.leaves(caches_ref), jax.tree.leaves(caches_i)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL)


def test_interpret_decode_matches_reference_mla():
    """Same decode parity through the weight-absorbed MLA path (latent-space
    flash decode with mismatched qk/v dims)."""
    cfg = reduce_config(get_config("minicpm3-4b"))
    assert cfg.use_mla
    params = M.init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0,
                              cfg.vocab_size)
    _, caches, pages = paged_prefill(cfg, params, {"tokens": toks[:, :-1]},
                                     24)
    lg_ref, _ = M.decode_step(cfg, params, caches, toks[:, -1:],
                              jnp.int32(23), pages=pages)
    lg_i, _ = M.decode_step(cfg.with_(kernel_mode="interpret"), params,
                            caches, toks[:, -1:], jnp.int32(23), pages=pages)
    np.testing.assert_allclose(np.asarray(lg_i, np.float32),
                               np.asarray(lg_ref, np.float32), atol=1e-2)


def test_quantize_params_structure(edge):
    cfg, params, _ = edge
    qp = M.quantize_params(cfg, params)
    layer0 = qp["stages"][0]["0"]
    assert isinstance(layer0["mixer"]["wq"], QTensor)
    assert layer0["mixer"]["wq"].q.dtype == jnp.int8
    assert isinstance(qp["lm_head"], QTensor)
    # norms / embeddings untouched; idempotent on re-application
    assert not isinstance(qp["embed"], QTensor)
    assert not isinstance(layer0["norm1"]["scale"], QTensor)
    qp2 = M.quantize_params(cfg, qp)
    assert qp2["lm_head"] is qp["lm_head"]


def test_w8a8_forward_close_to_fp32(edge):
    """End-to-end int8 path stays within quantization error of fp32 and
    mostly agrees on argmax."""
    cfg, params, batch = edge
    h_ref, _, _ = M.forward_hidden(cfg, params, batch, mode="train")
    lg_ref = np.asarray(M.lm_logits(cfg, params, h_ref), np.float32)
    cfg_q = cfg.with_(quant="w8a8")
    qp = M.quantize_params(cfg_q, params)
    h_q, _, _ = M.forward_hidden(cfg_q, qp, batch, mode="train")
    lg_q = np.asarray(M.lm_logits(cfg_q, qp, h_q), np.float32)
    rel = np.abs(lg_q - lg_ref) / (np.abs(lg_ref) + 1.0)
    assert np.median(rel) < 0.05, np.median(rel)
    agree = np.mean(np.argmax(lg_q[:, :, : cfg.vocab_size], -1)
                    == np.argmax(lg_ref[:, :, : cfg.vocab_size], -1))
    assert agree > 0.7, agree


def test_w8a8_prefill_decode(edge):
    """Quantized weights flow through prefill + the paged decode step."""
    cfg, params, batch = edge
    cfg_q = cfg.with_(quant="w8a8")
    qp = M.quantize_params(cfg_q, params)
    toks = batch["tokens"]
    lg, caches, pages = paged_prefill(cfg_q, qp, {"tokens": toks[:, :-1]},
                                      toks.shape[1])
    lg2, _ = M.decode_step(cfg_q, qp, caches, toks[:, -1:],
                           jnp.int32(toks.shape[1] - 1), pages=pages)
    assert np.isfinite(np.asarray(lg2, np.float32)).all()


def test_w8a8_tied_embeddings_head_quantized():
    """Tied-head configs (gemma) get an int8 copy of embed.T for the LM head
    GEMM — the embedding table itself stays float (it is a gather)."""
    cfg = reduce_config(get_config("gemma3-4b")).with_(quant="w8a8")
    assert cfg.tie_embeddings
    params = M.init(cfg, jax.random.PRNGKey(0))
    qp = M.quantize_params(cfg, params)
    assert isinstance(qp["lm_head_q"], QTensor)
    assert not isinstance(qp["embed"], QTensor)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0,
                              cfg.vocab_size)
    h, _, _ = M.forward_hidden(cfg, qp, {"tokens": toks}, mode="train")
    lg = M.lm_logits(cfg, qp, h)
    assert np.isfinite(np.asarray(lg, np.float32)).all()
    # float path (no lm_head_q) still works for tied configs
    lg_f = M.lm_logits(cfg.with_(quant="none"), params, h)
    assert lg_f.shape == lg.shape
