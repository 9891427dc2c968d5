"""Oracles over the paged KV cache, shared by the model, kernel-mode,
paging and serving tests.

- :func:`paged_prefill`: a whole-prompt ``M.prefill`` written into fresh
  paged pools with ``M.write_prefill`` through an identity page table —
  the starting point of a paged ``M.decode_step`` loop.
- :func:`reference_greedy`: that prefill, then one paged ``decode_step``
  per token: the single-sequence oracle every engine output must match
  token for token.
- :func:`attend_rows`: single-token decode attention over dense rows
  through the model's jnp ``attend`` core, the oracle the paged kernels
  and their gather oracle are held to."""
import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.layers import attend

#: rows per page of the oracles' pools
PAGE = 16


def identity_pages(B: int, npp: int):
    """[B, npp] page tables: slot ``b`` owns pages ``1 + b * npp`` onwards
    in order (page 0 is the trash page)."""
    return jnp.arange(1, B * npp + 1, dtype=jnp.int32).reshape(B, npp)


def paged_prefill(cfg, params, batch, rows: int, page: int = PAGE):
    """Whole-prompt prefill of every sequence of ``batch``, written into
    fresh pools of ``rows`` logical rows a sequence.  Returns (last-token
    logits, caches, [B, npp] page tables)."""
    B = batch["tokens"].shape[0]
    npp = -(-rows // page)
    pages = identity_pages(B, npp)
    logits, small = M.prefill(cfg, params, batch)
    caches = M.init_paged_cache(cfg, B, B * npp + 1, page)
    for b in range(B):
        one = jax.tree.map(lambda a: a[:, b:b + 1], small)
        caches = M.write_prefill(cfg, caches, one, pages[b], b)
    return logits, caches, pages


def reference_greedy(cfg, params, prompt, max_new):
    """Greedy tokens of one prompt: :func:`paged_prefill`, then one paged
    ``decode_step`` per token."""
    plen = len(prompt)
    logits, caches, pages = paged_prefill(
        cfg, params, {"tokens": jnp.asarray([prompt], jnp.int32)},
        plen + max_new)
    cur = int(jnp.argmax(logits[0, -1, : cfg.vocab_size]))
    out = [cur]
    for step in range(max_new - 1):
        logits, caches = M.decode_step(cfg, params, caches,
                                       jnp.asarray([[cur]], jnp.int32),
                                       jnp.int32(plen + step), pages=pages)
        cur = int(jnp.argmax(logits[0, -1, : cfg.vocab_size]))
        out.append(cur)
    return out


def attend_rows(q, k, v, pos, start=None, *, window: int = 0,
                softcap: float = 0.0):
    """Decode attention of q [B, H, d] at row ``pos`` over dense rows k/v
    [B, S, K, d] -> [B, H, dv]: rows ``[start, pos]`` are live (rows below
    ``start`` get a negative position, which ``attend`` never attends),
    optionally within a causal ``window``."""
    B, S = k.shape[:2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    start = jnp.broadcast_to(jnp.asarray(0 if start is None else start,
                                         jnp.int32), (B,))
    rows = jnp.arange(S, dtype=jnp.int32)[None]
    k_pos = jnp.where(rows >= start[:, None], rows, -1)
    o = attend(q[:, None], k, v, pos[:, None], k_pos, causal=True,
               window=window, softcap=softcap)
    return o[:, 0]
