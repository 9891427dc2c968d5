"""Reference (pure-jnp) transformer layers.

``cfg.kernel_mode`` selects the implementation of every GEMM-heavy op:

- ``reference`` — plain jnp einsum/matmul (dry-run oracle; the lowered HLO is
  analyzable by ``cost_analysis`` and the Pallas kernels validate against it)
- ``interpret`` — the Pallas CGRA block-GEMM / flash-attention kernels run
  through the interpreter (CPU validation of the exact kernel math)
- ``pallas`` — the compiled TPU kernels (the serving hot path)

All dense projections funnel through :func:`dense_proj` (which also serves
int8 ``QTensor`` weights, ``cfg.quant == "w8a8"``), forward/prefill
attention through :func:`dispatch_attend`, and single-token decode
attention through :func:`dispatch_attend_decode` (the flash-decode kernel
over the paged KV pools); see DESIGN.md §2/§6.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

import functools
import math

from repro.configs.base import ArchConfig
from repro.core import round_up
from repro.core.gemm import cgra_gemm, cgra_gemm_w8a8
from repro.core.quant import QTensor
from repro.kernels.ops import attend_decode as kernel_attend_decode
from repro.kernels.ops import attention as kernel_attention
from repro.launch.sharding import constrain, current_mesh, tp_shard_map
from repro.models.params import ParamSpec

F32 = jnp.float32
NEG_INF = -1e9

# When set (see launch/dryrun.py), `attend` replaces the score/softmax core
# with a stand-in that touches only q/k/v/o-sized tensors — i.e. exactly the
# HBM traffic of the Pallas flash kernel.  cost_analysis of this variant
# gives the flash-adjusted memory roofline term; never used for real math.
import contextvars

ATTN_STUB: contextvars.ContextVar = contextvars.ContextVar("attn_stub",
                                                           default=False)


# ---------------------------------------------------------------------------
# Dense projection — the single GEMM choke point of the model.
#
# Every weight-activation matmul (q/k/v/o projections, MLP, MLA low-rank
# projections, LM head) funnels through ``dense_proj`` so ``cfg.kernel_mode``
# selects the jnp reference path, the Pallas interpret path (CPU validation)
# or the compiled TPU block-GEMM — and pre-quantized ``QTensor`` weights
# (``cfg.quant == "w8a8"``, see ``models.model.quantize_params``) serve
# through the packed int8 kernel with its fused dequant epilogue.  MoE expert
# GEMMs stay on their einsum dispatch path (batched over experts).
# ---------------------------------------------------------------------------


def _tp_mesh(cfg):
    """(mesh, tp) when Pallas kernel calls must run per-shard under
    ``shard_map``: an activation mesh is active at trace time and
    ``cfg.kernel_mode`` routes through ``pallas_call`` (which has no SPMD
    partitioning rules — the reference jnp paths partition under XLA's auto
    partitioner and need none of this)."""
    mesh = current_mesh()
    if mesh is None or cfg.kernel_mode == "reference":
        return None, 1
    tp = dict(mesh.shape).get("model", 1)
    return (mesh, tp) if tp > 1 else (None, 1)


def _tp_gemm(mesh, tp, gemm, x, w, shard):
    """One GEMM under ``shard_map`` on the `model` axis.

    ``shard=("col", blocks)``: w [K, N] split on N into ``blocks`` logical
    column blocks (head / ffn / vocab units) — each device computes its
    output slice, no collective.  ``shard=("row", blocks)``: x/w split on the
    contraction dim K, partial GEMMs summed with an f32 psum (16-bit
    all-reduces trip an XLA CPU promotion-pass bug; see _moe_expert_block).
    Anything else (no hint, or ``blocks % tp != 0`` — matching the
    divisibility fallback that left the weight replicated): every device
    runs the whole GEMM replicated."""
    from jax.sharding import PartitionSpec as P
    nd = x.ndim
    kind, blocks = shard if shard else (None, 0)
    if kind == "col" and blocks % tp == 0:
        out_spec = P(*([None] * (nd - 1) + ["model"]))
        return tp_shard_map(gemm, mesh, (P(), P(None, "model")), out_spec)(x, w)
    if kind == "row" and blocks % tp == 0:
        def body(xs, ws):
            o = gemm(xs, ws)
            return lax.psum(o.astype(F32), "model").astype(o.dtype)
        x_spec = P(*([None] * (nd - 1) + ["model"]))
        return tp_shard_map(body, mesh, (x_spec, P("model", None)), P())(x, w)
    return tp_shard_map(gemm, mesh, (P(), P()), P())(x, w)


def dense_proj(cfg: ArchConfig, x, w, out_shape: tuple = (), out_dtype=None,
               shard: tuple | None = None):
    """x: [..., K] @ w -> [..., N] (or [..., *out_shape] with N = prod).

    ``w`` is either a float weight whose dims reshape row-major to [K, N]
    (e.g. wq: [D,H,dh] -> [D, H*dh]; wo: [H,dh,D] -> [H*dh, D] with the
    caller flattening x's head dims), or a ``QTensor`` holding the int8
    quantization of that same [K, N] reshape.  ``out_dtype`` overrides the
    store dtype of the accumulator (default: the compute dtype) — the
    logits head requests f32 so full precision survives to the sampler.

    ``shard=("col"|"row", blocks)`` is the tensor-parallel hint, used only
    when a mesh is active *and* the GEMM routes through Pallas (see
    ``_tp_gemm``); it must mirror how ``resolve_pspec`` placed the weight —
    "col" for output-dim sharding (wq/wk/wv/w_gate/w_up/lm_head), "row" for
    contraction-dim sharding (wo/w_down), ``blocks`` the logical unit count
    (heads / kv_heads / d_ff / padded_vocab) whose divisibility by tp gates
    the sharding.  QTensor weights are always placed replicated under a mesh
    (see ``model.shard_params``), so they take the replicated path.
    """
    Kdim = x.shape[-1]
    mesh, tp = _tp_mesh(cfg)
    if isinstance(w, QTensor):
        w2 = QTensor(w.q.reshape(Kdim, -1), w.scale.reshape(1, -1))
        gemm = functools.partial(cgra_gemm_w8a8, mode=cfg.kernel_mode,
                                 out_dtype=out_dtype or cfg.compute_dtype)
        shard = None  # int8 TP would re-quantize activations per shard
    else:
        w2 = w.reshape(Kdim, -1).astype(cfg.compute_dtype)
        gemm = functools.partial(cgra_gemm, mode=cfg.kernel_mode,
                                 out_dtype=out_dtype)
    if mesh is not None:
        out = _tp_gemm(mesh, tp, gemm, x, w2, shard)
    else:
        out = gemm(x, w2)
    if out_shape:
        out = out.reshape(*out.shape[:-1], *out_shape)
    return out


def dispatch_attend(cfg: ArchConfig, q, k, v, q_pos, k_pos, *, causal: bool,
                    window: int = 0, chunk: int = 0, softcap: float = 0.0):
    """kernel_mode-aware attention core.  Layout as ``attend``:
    q [B,Sq,H,d], k/v [B,Sk,K,d] -> [B,Sq,H,d].

    The flash kernel path covers the contiguous self/cross-attention pattern
    used by forward/prefill (positions are aranges with the last query
    aligned with the last key), preserving GQA grouping, sliding windows and
    logit softcap.
    The jnp ``attend`` stays the oracle for ``kernel_mode="reference"`` and
    for the roofline ATTN_STUB traffic stand-in; MLA keeps ``attend``
    unconditionally (its q/v head dims differ, which the prefill kernel
    accumulator does not model).

    Differentiability: the block GEMMs are trainable in every mode
    (``cgra_matmul`` carries a custom VJP) but the flash kernel has no VJP —
    train/finetune with ``kernel_mode="reference"``; interpret/pallas are
    the inference (serving) modes.
    """
    if cfg.kernel_mode == "reference" or ATTN_STUB.get():
        return attend(q, k, v, q_pos, k_pos, causal=causal, window=window,
                      chunk=chunk, softcap=softcap)
    call = functools.partial(kernel_attention, causal=causal, window=window,
                             softcap=softcap, mode=cfg.kernel_mode)
    qT, kT, vT = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    mesh, tp = _tp_mesh(cfg)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        # per-device head shards keep the GQA fold intact (H/tp queries over
        # K/tp KV heads, same group size); non-divisible head counts fall
        # back to every device running the whole kernel replicated
        hs = "model" if (qT.shape[1] % tp == 0 and kT.shape[1] % tp == 0) \
            else None
        spec = P(None, hs, None, None)
        o = tp_shard_map(call, mesh, (spec, spec, spec), spec)(qT, kT, vT)
    else:
        o = call(qT, kT, vT)
    return o.transpose(0, 2, 1, 3)


def dispatch_attend_decode(cfg: ArchConfig, q, k, v, pos, start, *, pages,
                           layer, softcap: float = 0.0, scale=None,
                           dv: int | None = None):
    """kernel_mode-aware single-token decode core.

    q [B,1,H,dq] and head-major page pools k/v stacked over layers
    [L,P,K,ps,d], read at the int32 scalar ``layer`` through the [B, npp]
    page tables ``pages`` -> [B,1,H,dv].  The stacked pool is the kernel's
    own layout, so the serving hot path never copies it, nor slices a layer
    out of it.
    ``pos``/``start`` are the per-slot [B] validity bounds (logical row of
    the current token / first live row — sliding-window layers pass
    ``max(0, pos - window + 1)``); ``dv`` narrows the value read (MLA
    passes one fused pool as both k and v).
    Routes to the jnp oracle (``reference``) or the flash-decode Pallas
    kernel (``interpret`` | ``pallas``), which streams only live pages.
    Under a mesh the kernel runs per-KV-head-shard inside ``shard_map``
    (page tables / validity bounds replicated, head fold untouched — each
    shard keeps its full query groups); MLA's fused single-KV-head pool and
    other non-divisible head counts run replicated.
    """
    q0 = q[:, 0]
    call = functools.partial(kernel_attend_decode, softcap=softcap,
                             scale=scale, dv=dv, mode=cfg.kernel_mode)
    mesh, tp = _tp_mesh(cfg)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        kax = 2  # stacked pool [L,P,K,ps,d]
        hs = "model" if (q0.shape[1] % tp == 0 and k.shape[kax] % tp == 0) \
            else None
        qspec = P(None, hs, None)            # q [B, H, dq]
        kvspec = P(*(hs if i == kax else None for i in range(k.ndim)))
        body = lambda qq, kk, vv, pp, ss, pg, ly: call(qq, kk, vv, pp, ss,
                                                       pages=pg, layer=ly)
        o = tp_shard_map(
            body, mesh,
            (qspec, kvspec, kvspec, P(None), P(None), P(None, None), P()),
            qspec)(q0, k, v, pos, start, pages, layer)
    else:
        o = call(q0, k, v, pos, start, pages=pages, layer=layer)
    return o[:, None]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ArchConfig) -> dict:
    if cfg.norm_type == "rmsnorm":
        return {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones")}
    if cfg.norm_type == "layernorm":
        return {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones"),
                "bias": ParamSpec((cfg.d_model,), ("embed",), "zeros")}
    return {}  # layernorm_nonparam


def apply_norm(cfg: ArchConfig, p: dict, x):
    xf = x.astype(F32)
    if cfg.norm_type == "rmsnorm":
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        return (xf * p["scale"].astype(F32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    xf = (xf - mu) * lax.rsqrt(var + 1e-6)
    if cfg.norm_type == "layernorm":
        xf = xf * p["scale"].astype(F32) + p["bias"].astype(F32)
    return xf.astype(x.dtype)


def rms_only(x, scale, eps=1e-6):
    xf = x.astype(F32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale.astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: [B, S, n, d] (d even), positions: [S] (shared) or [B, S] (per-row,
    continuous-batching decode where every slot sits at its own offset)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = positions.astype(F32)[..., :, None] * freq  # [S, half] | [B, S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # broadcasts over B and heads
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (reference).  Supports causal / bidirectional, sliding
# window, GQA grouping, optional query chunking (bounds the score-matrix
# footprint — "flash attention in jnp") and a numerically-identical
# unchunked path used for the roofline cost compiles.
# ---------------------------------------------------------------------------

def _valid_mask(q_pos, k_pos, causal: bool, window: int):
    """Boolean key-validity mask from absolute positions.

    ``q_pos``/``k_pos``: [S] (shared) or [B, S] (per-row — continuous
    batching, where every slot carries its own left-pad offset).  Returns
    [Sq, Sk] or [B, Sq, Sk].  Keys at negative positions are left-pad rows
    (positions are ``arange - start``) and are invalid for every query.
    """
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = kp >= 0
    if causal:
        m = m & (kp <= qp)
    if window:
        m = m & (kp > qp - window)
    return m


def attend(q, k, v, q_pos, k_pos, *, causal: bool, window: int = 0,
           chunk: int = 0, softcap: float = 0.0):
    """q: [B,Sq,H,dq], k: [B,Sk,K,dq], v: [B,Sk,K,dv] -> [B,Sq,H,dv].

    GQA: H q-heads grouped onto K kv-heads (H % K == 0).  Positions may be
    shared ([S]) or per-row ([B, S]); queries whose every key is masked
    (e.g. left-pad rows) return zeros, matching the flash kernels.
    """
    B, Sq, H, dq = q.shape
    K = k.shape[2]
    G = H // K
    dv = v.shape[-1]
    scale = dq ** -0.5
    qg = q.reshape(B, Sq, K, G, dq)

    def _block(qb, q_pos_b):
        # qb: [B, sq, K, G, dq]; q_pos_b: [sq] or [B, sq]
        if ATTN_STUB.get():  # flash-traffic stand-in: q/k/v read, o write
            vm = jnp.mean(v, axis=1)  # [B,K,dv]
            km = jnp.sum(jnp.mean(k, axis=1), -1, keepdims=True)  # consume k
            qs = jnp.sum(qb, axis=-1, keepdims=True) * 1e-9  # consume q
            return (qs + (vm + km * 1e-9)[:, None, :, None, :]).astype(v.dtype)
        with jax.named_scope("attn_core"):
            s = jnp.einsum("bskgd,btkd->bkgst", qb, k,
                           preferred_element_type=F32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            mask = _valid_mask(q_pos_b, k_pos, causal, window)
            if mask.ndim == 2:
                mask = mask[None]
            mb = mask[:, None, None]  # [B|1, 1, 1, sq, Sk] vs s [B,K,G,sq,Sk]
            s = jnp.where(mb, s, NEG_INF)
            s = jax.nn.softmax(s, axis=-1)
            s = jnp.where(mb, s, 0.0)  # all-masked rows -> zeros, not 1/Sk
            return jnp.einsum("bkgst,btkd->bskgd", s.astype(v.dtype), v,
                              preferred_element_type=F32).astype(v.dtype)

    if chunk and Sq > chunk:
        # pad the tail chunk so ragged Sq still runs blockwise (the padded
        # query rows are computed and sliced off, like the Pallas grid pad)
        pad = (-Sq) % chunk
        qgp = jnp.pad(qg, ((0, 0), (0, pad)) + ((0, 0),) * 3)
        nb = (Sq + pad) // chunk
        qb = qgp.reshape(B, nb, chunk, K, G, dq).transpose(1, 0, 2, 3, 4, 5)
        if q_pos.ndim == 2:  # per-row positions: [B, Sq] -> [nb, B, chunk]
            pp = jnp.pad(q_pos, ((0, 0), (0, pad)), mode="edge")
            pb = pp.reshape(B, nb, chunk).transpose(1, 0, 2)
        else:
            pp = jnp.pad(q_pos, (0, pad), mode="edge")
            pb = pp.reshape(nb, chunk)
        out = lax.map(lambda args: _block(*args), (qb, pb))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq + pad, K, G, dv)
        out = out[:, :Sq]
    else:
        out = _block(qg, q_pos)
    return out.reshape(B, Sq, H, dv)


# ---------------------------------------------------------------------------
# GQA attention layer (global or sliding-window local), with KV cache decode.
# ---------------------------------------------------------------------------

def attn_specs(cfg: ArchConfig, cross: bool = False) -> dict:
    H, K, dh = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim
    D = cfg.d_model
    p = {
        "wq": ParamSpec((D, H, dh), ("embed", "heads", "qk")),
        "wk": ParamSpec((D, K, dh), ("embed", "kv_heads", "qk")),
        "wv": ParamSpec((D, K, dh), ("embed", "kv_heads", "qk")),
        "wo": ParamSpec((H, dh, D), ("heads", "qk", "embed")),
    }
    if getattr(cfg, "use_qk_norm", False):
        p["q_norm"] = ParamSpec((dh,), (None,), "ones")
        p["k_norm"] = ParamSpec((dh,), (None,), "ones")
    return p


def _qkv(cfg, p, xq, xkv):
    H, K, dh = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense_proj(cfg, xq, p["wq"], (H, dh), shard=("col", H))
    k = dense_proj(cfg, xkv, p["wk"], (K, dh), shard=("col", K))
    v = dense_proj(cfg, xkv, p["wv"], (K, dh), shard=("col", K))
    if "q_norm" in p:
        q = rms_only(q, p["q_norm"])
        k = rms_only(k, p["k_norm"])
    # pin batch/head sharding at the attention boundary — without this the
    # partitioner replicated pure-FSDP score tensors over the model axis
    # (measured: 64 GiB f32 scores on deepseek; EXPERIMENTS.md §Perf)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def attn_forward(cfg: ArchConfig, p: dict, x, positions, *, local: bool,
                 attn_chunk: int = 0):
    """Training / encoder self-attention.  x: [B,S,D]."""
    q, k, v = _qkv(cfg, p, x, x)
    theta = cfg.rope_theta if not local else 10_000.0
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    causal = cfg.kind == "decoder"
    window = cfg.window_size if local else 0
    o = dispatch_attend(cfg, q, k, v, positions, positions, causal=causal,
                        window=window, chunk=attn_chunk,
                        softcap=cfg.logit_softcap)
    o = constrain(o, ("batch", None, "heads", None))
    return dense_proj(cfg, o.reshape(*o.shape[:-2], -1), p["wo"],
                      shard=("row", cfg.padded_heads))


def attn_cache_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    K, dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": ParamSpec((batch, seq, K, dh), ("batch", "kv_seq", "kv_heads", "qk"), "zeros"),
        "v": ParamSpec((batch, seq, K, dh), ("batch", "kv_seq", "kv_heads", "qk"), "zeros"),
    }


def _page_row_write(pools: tuple, new_rows: tuple, pages, pos, layer):
    """Write one row per sequence into layer ``layer`` of head-major page
    pools stacked over layers, in place.

    pools: [L, P, K, ps, d] each (k and v, written in one loop); new_rows:
    [B, K, d] each; pages: [B, npp]; pos: [B]; layer: int32 scalar.
    Logical row ``pos`` of sequence ``b`` lands at
    ``pool[layer, pages[b, pos // ps], :, pos % ps]``.  Rows whose page
    index would fall off the table are dropped, never clamped (the engine
    errors on capacity overrun before this can matter).

    A loop over the sequences, each step a read-modify-write of the
    ``[K, tile, d]`` block of whole sublane tiles that holds the row, with
    ``dynamic_slice`` / ``dynamic_update_slice``: XLA updates the stack in
    place and keeps its row-major layout, which the attention kernels read.
    A scatter (``.at[].set`` with the page and row indices apart) lowers as
    a transpose and a copy of the whole pool; a single-row update makes the
    TPU compiler lay the stack out head-minor and copy it to and from the
    kernels' layout.  A loop step's read and write see one version of the
    pool; unrolled, XLA fuses each read with the write before it, and so
    copies the pool."""
    _, _, _, ps, _ = pools[0].shape
    npp = pages.shape[1]
    rows = tuple(r.astype(p.dtype) for r, p in zip(new_rows, pools))
    ipage = pos // ps
    ok = ipage < npp
    page = jnp.take_along_axis(pages, jnp.minimum(ipage, npp - 1)[:, None],
                               axis=1)[:, 0]
    tile = math.gcd(ps, 16)  # a bf16 sublane tile, or the page
    t = jnp.arange(tile, dtype=jnp.int32)

    def write(b, pools):
        off = pos[b] % ps
        at = (layer, page[b], 0, off - off % tile, 0)
        hit = (ok[b] & (t == off % tile))[:, None]
        out = []
        for pool, row in zip(pools, rows):
            _, _, K, _, d = pool.shape
            old = lax.dynamic_slice(pool, at, (1, 1, K, tile, d))
            new = jnp.where(hit, row[b][None, None, :, None], old)
            out.append(lax.dynamic_update_slice(pool, new, at))
        return tuple(out)

    return lax.fori_loop(0, pos.shape[0], write, tuple(pools))


def attn_prefill(cfg: ArchConfig, p: dict, x, positions, *, local: bool,
                 attn_chunk: int = 0):
    """Whole-prompt prefill.  Returns (out, cache): the prompt's full
    post-RoPE KV rows, ``{"k","v"}`` [B, S, K, dh] — sliding-window layers
    included, since the paged cache stores every row and windows at decode
    time through the validity bound.  ``positions``: [S] absolute positions
    of the prompt rows."""
    q, k, v = _qkv(cfg, p, x, x)
    theta = cfg.rope_theta if not local else 10_000.0
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    window = cfg.window_size if local else 0
    o = dispatch_attend(cfg, q, k, v, positions, positions, causal=True,
                        window=window, chunk=attn_chunk,
                        softcap=cfg.logit_softcap)
    out = dense_proj(cfg, o.reshape(*o.shape[:-2], -1), p["wo"],
                     shard=("row", cfg.padded_heads))
    return out, {"k": k, "v": v}


def _page_rows_write(pools: tuple, new_rows: tuple, pages, pos0, n, layer):
    """Write a *chunk* of rows per sequence into layer ``layer`` of stacked
    head-major page pools, in place.

    pools: [L, P, K, ps, d] each (k and v, written in one loop); new_rows:
    [B, C, K, d] each; pages: [B, npp]; pos0/n: [B]; layer: int32 scalar.
    Chunk row ``i`` of sequence ``b`` is logical row ``r = pos0[b] + i`` and
    lands at ``pool[layer, pages[b, r // ps], :, r % ps]``.  Rows at
    ``i >= n[b]`` (the chunk's padding) and rows whose page index would fall
    off the table are dropped, never clamped — same contract as
    :func:`_page_row_write`.

    A loop over each sequence's pages that a run of ``C`` rows can touch:
    each step reads the page's ``[K, ps, d]`` tile, merges its chunk rows
    and writes it back (``dynamic_slice`` / ``dynamic_update_slice``, in
    place, as in :func:`_page_row_write`)."""
    _, _, _, ps, _ = pools[0].shape
    B, C = new_rows[0].shape[:2]
    npp = pages.shape[1]
    nj = (C + ps - 2) // ps + 1  # pages a run of C rows can touch
    # head-major chunks, padded by a page each side so that every page's
    # rows are one in-bounds slice: page j of b starts at chunk row
    # j * ps - pos0[b] % ps
    rows = tuple(jnp.pad(r.astype(p.dtype).transpose(0, 2, 1, 3),
                         ((0, 0), (0, 0), (ps, ps), (0, 0)))
                 for r, p in zip(new_rows, pools))
    t = jnp.arange(ps, dtype=jnp.int32)

    def write(bj, pools):
        b, j = bj // nj, bj % nj
        ipage, off = pos0[b] // ps + j, pos0[b] % ps
        i = j * ps - off + t  # chunk row of each row of the page
        ok = (i >= 0) & (i < jnp.minimum(n[b], C)) & (ipage < npp)
        at = (layer, pages[b, jnp.minimum(ipage, npp - 1)], 0, 0, 0)
        out = []
        for pool, r in zip(pools, rows):
            _, _, K, _, d = pool.shape
            old = lax.dynamic_slice(pool, at, (1, 1, K, ps, d))
            new = lax.dynamic_slice(r, (b, 0, j * ps - off + ps, 0),
                                    (1, K, ps, d))
            out.append(lax.dynamic_update_slice(
                pool, jnp.where(ok[:, None], new, old[0])[None], at))
        return tuple(out)

    return lax.fori_loop(0, B * nj, write, tuple(pools))


def attn_chunk_prefill(cfg: ArchConfig, p: dict, cache: dict, x, positions, *,
                       local: bool, pages, chunk_len, layer):
    """Chunked prefill over a paged past: one fixed-size prompt chunk.

    x: [B, C, D] — a size-C chunk buffer holding ``chunk_len`` valid prompt
    rows (the rest is padding); ``positions``: [C] or [B, C] absolute
    positions (``past_len + arange(C)``); cache: page pools stacked over
    layers [L, P, K, ps, dh], this layer at the int32 scalar ``layer``;
    pages: [B, npp] page tables; ``chunk_len``: scalar or [B] int32.

    Writes the chunk's post-RoPE KV straight through the page table (no
    dense gather of the past — the cached prefix stays in its pages) and
    attends the query chunk over logical rows ``[0, past_len + chunk_len)``
    via the paged flash-attention layout.  Padding rows beyond ``chunk_len``
    produce garbage outputs the caller must ignore (the engine only reads
    the last valid row); their KV writes are dropped.  Returns
    (out, updated stacked page pools)."""
    B, C = x.shape[0], x.shape[1]
    q, k_new, v_new = _qkv(cfg, p, x, x)
    theta = cfg.rope_theta if not local else 10_000.0
    positions = jnp.asarray(positions, jnp.int32)
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (B, C))
    q = rope(q, positions, theta)
    k_new = rope(k_new, positions, theta)
    pages = jnp.asarray(pages, jnp.int32)
    chunk_len = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (B,))
    pos0 = positions[:, 0]
    k, v = _page_rows_write((cache["k"], cache["v"]), (k_new, v_new), pages,
                            pos0, chunk_len, layer)
    window = cfg.window_size if local else 0
    call = functools.partial(kernel_attention, window=window,
                             softcap=cfg.logit_softcap, mode=cfg.kernel_mode)
    qT = q.transpose(0, 2, 1, 3)
    mesh, tp = _tp_mesh(cfg)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        hs = "model" if (qT.shape[1] % tp == 0 and k.shape[2] % tp == 0) \
            else None
        body = lambda qq, kk, vv, pg, qs, kl, ly: call(
            qq, kk, vv, pages=pg, q_start=qs, k_len=kl, layer=ly)
        heads = P(None, hs, None, None)  # q [B,H,C,d]
        pools = P(None, None, hs, None, None)  # [L,P,K,ps,d]
        o = tp_shard_map(
            body, mesh,
            (heads, pools, pools, P(None, None), P(None), P(None), P()),
            heads)(qT, k, v, pages, pos0, pos0 + chunk_len, layer)
    else:
        o = call(qT, k, v, pages=pages, q_start=pos0, k_len=pos0 + chunk_len,
                 layer=layer)
    o = o.transpose(0, 2, 1, 3)
    o = constrain(o, ("batch", None, "heads", None))
    out = dense_proj(cfg, o.reshape(*o.shape[:-2], -1), p["wo"],
                     shard=("row", cfg.padded_heads))
    return out, {"k": k, "v": v}


def attn_decode(cfg: ArchConfig, p: dict, cache: dict, x, pos, *, local: bool,
                pages, layer):
    """One-token decode.  x: [B,1,D]; pos: scalar int32 or [B] int32
    (logical row of the current token, per batch slot — continuous batching
    runs every slot at its own offset).

    The cache is page pools stacked over layers [L, P, K, ps, dh], shared
    across the batch, this layer at the int32 scalar ``layer``; pages:
    [B, npp] page tables.  The new row is written in place at the
    page-table row for ``pos`` (a row past the table's end is dropped,
    never clamped — the engine refuses to decode past capacity first) and
    attention follows the table.  Sliding-window layers store full rows
    like global ones and window via the validity lower bound
    ``start = max(0, pos - window + 1)``.

    The attention core routes through :func:`dispatch_attend_decode`;
    RoPE is pre-applied to cached keys, so scores need no position
    reconstruction.
    """
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))  # one per slot
    q, k_new, v_new = _qkv(cfg, p, x, x)
    theta = cfg.rope_theta if not local else 10_000.0
    rp = pos[:, None]
    q = rope(q, rp, theta)
    k_new = rope(k_new, rp, theta)
    window = cfg.window_size if local else 0
    pages = jnp.asarray(pages, jnp.int32)
    k, v = _page_row_write((cache["k"], cache["v"]),
                           (k_new[:, 0], v_new[:, 0]), pages, pos, layer)
    start = jnp.maximum(pos - window + 1, 0) if window else None
    o = dispatch_attend_decode(cfg, q, k, v, pos, start, pages=pages,
                               layer=layer, softcap=cfg.logit_softcap)
    H = q.shape[2]
    o = o.reshape(B, 1, H * v.shape[-1])
    out = dense_proj(cfg, o, p["wo"], shard=("row", cfg.padded_heads))
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 / MiniCPM3 style)
# ---------------------------------------------------------------------------

def mla_specs(cfg: ArchConfig) -> dict:
    D, H = cfg.d_model, cfg.padded_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq_a": ParamSpec((D, qr), ("embed", "lora")),
        "q_norm": ParamSpec((qr,), (None,), "ones"),
        "wq_b": ParamSpec((qr, H, dn + dr), ("lora", "heads", "qk")),
        "wkv_a": ParamSpec((D, kvr + dr), ("embed", "lora")),
        "kv_norm": ParamSpec((kvr,), (None,), "ones"),
        "wkv_b": ParamSpec((kvr, H, dn + dv), ("lora", "heads", "qk")),
        "wo": ParamSpec((H, dv, D), ("heads", "qk", "embed")),
    }


def _mla_q(cfg, p, x, positions):
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = rms_only(dense_proj(cfg, x, p["wq_a"]), p["q_norm"])
    q = dense_proj(cfg, cq, p["wq_b"], (cfg.padded_heads, dn + dr),
                   shard=("col", cfg.padded_heads))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg, p, x, positions):
    kvr, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv = dense_proj(cfg, x, p["wkv_a"])
    latent, k_rope = ckv[..., :kvr], ckv[..., kvr:]
    latent = rms_only(latent, p["kv_norm"])
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return latent, k_rope  # [B,S,kvr], [B,S,dr]


def mla_forward(cfg: ArchConfig, p: dict, x, positions, attn_chunk: int = 0):
    dn, dv = cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    latent, k_rope = _mla_latent(cfg, p, x, positions)
    kv = dense_proj(cfg, latent, p["wkv_b"], (cfg.padded_heads, dn + dv),
                    shard=("col", cfg.padded_heads))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    H = k_nope.shape[2]
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], k_rope.shape[:2] + (H, k_rope.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope_b], -1)
    # MLA stays on the jnp attend core: q/k head dim (dn+dr) != v head dim
    o = attend(q, k, v, positions, positions, causal=(cfg.kind == "decoder"),
               chunk=attn_chunk)
    return dense_proj(cfg, o.reshape(*o.shape[:-2], -1), p["wo"],
                      shard=("row", cfg.padded_heads))


def mla_cache_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    # one fused [latent | k_rope] pool per layer: decode reads it as both
    # keys (full width) and values (first kv_lora_rank columns), so the hot
    # path never concatenates or slices the pool
    return {
        "kv": ParamSpec((batch, seq, cfg.kv_lora_rank + cfg.qk_rope_dim),
                        ("batch", "kv_seq", None), "zeros"),
    }


def mla_prefill(cfg: ArchConfig, p: dict, x, positions, attn_chunk: int = 0):
    out = mla_forward(cfg, p, x, positions, attn_chunk)
    latent, k_rope = _mla_latent(cfg, p, x, positions)
    return out, {"kv": jnp.concatenate([latent,
                                        k_rope.astype(latent.dtype)], -1)}


def mla_decode(cfg: ArchConfig, p: dict, cache: dict, x, pos, *, pages):
    """Weight-absorbed MLA decode: attention runs in the latent space, so the
    per-step cost is O(S * kv_lora_rank) instead of O(S * H * head_dim) —
    the cached latent is never re-expanded.  (This is the paper's data-reuse
    insight applied to the KV cache.)

    The latent-space core is the flash-decode kernel in MQA form: queries
    ``[q_absorbed | q_rope]`` against the fused ``[latent | k_rope]`` pool,
    which is passed as *both* keys (full width, qk dim ``kvr +
    qk_rope_dim``) and values (first ``kvr`` columns, selected by the
    BlockSpec — no slicing copy).  The cache is a [P, ps, kvr+dr] page pool
    (read as a stack of one head-major [1, P, 1, ps, kvr+dr] pool of one
    latent head, at layer 0) written through the per-slot page tables
    ``pages``, exactly as in :func:`attn_decode`.
    """
    dn, dv = cfg.qk_nope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))  # one per slot
    rp = pos[:, None]
    q_nope, q_rope = _mla_q(cfg, p, x, rp)  # [B,1,H,dn],[B,1,H,dr]
    latent_new, k_rope_new = _mla_latent(cfg, p, x, rp)
    # out-of-capacity writes are dropped, never clamped onto the last row
    # (same invariant as attn_decode; the engine errors before this happens)
    row = jnp.concatenate([latent_new, k_rope_new.astype(latent_new.dtype)],
                          -1)[:, 0]
    pages = jnp.asarray(pages, jnp.int32)
    layer = jnp.int32(0)
    kv4, = _page_row_write((cache["kv"][None, :, None],), (row[:, None],),
                           pages, pos, layer)
    kv = kv4[0, :, 0]  # kv4 [1,P,1,ps,kvr+dr]: same array as k AND v
    wkv_b = p["wkv_b"].astype(cfg.compute_dtype)  # [kvr, H, dn+dv]
    wk, wv = wkv_b[..., :dn], wkv_b[..., dn:]
    # absorb: q_lat[b,h,r] = sum_d q_nope[b,h,d] wk[r,h,d]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk,
                       preferred_element_type=F32).astype(q_nope.dtype)
    q_cat = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], -1)
    o_lat = dispatch_attend_decode(
        cfg, q_cat, kv4, kv4, pos, None, pages=pages, layer=layer,
        scale=(dn + cfg.qk_rope_dim) ** -0.5, dv=kvr)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, wv,  # expand to v space
                   preferred_element_type=F32).astype(o_lat.dtype)
    out = dense_proj(cfg, o.reshape(*o.shape[:-2], -1), p["wo"],
                     shard=("row", cfg.padded_heads))
    return out, {"kv": kv}


# ---------------------------------------------------------------------------
# Cross-attention sub-block (Llama-3.2-Vision style)
# ---------------------------------------------------------------------------

def cross_attn_specs(cfg: ArchConfig) -> dict:
    p = attn_specs(cfg)
    p["gate"] = ParamSpec((), (), "zeros")  # tanh-gated residual
    return p


def cross_attn(cfg: ArchConfig, p: dict, x, img, img_kv=None):
    """x: [B,S,D] text hidden; img: [B,T,D] projected image embeddings.
    Returns (out, (k, v)) so decode can reuse the static cross KV."""
    H, K, dh = cfg.padded_heads, cfg.num_kv_heads, cfg.head_dim
    if img_kv is None:
        k = dense_proj(cfg, img, p["wk"], (K, dh), shard=("col", K))
        v = dense_proj(cfg, img, p["wv"], (K, dh), shard=("col", K))
        if "q_norm" in p:
            k = rms_only(k, p["k_norm"])
    else:
        k, v = img_kv
    q = dense_proj(cfg, x, p["wq"], (H, dh), shard=("col", H))
    if "q_norm" in p:
        q = rms_only(q, p["q_norm"])
    Sq, T = q.shape[1], k.shape[1]
    o = dispatch_attend(cfg, q, k, v, jnp.arange(Sq), jnp.arange(T),
                        causal=False)
    o = dense_proj(cfg, o.reshape(*o.shape[:-2], -1), p["wo"],
                   shard=("row", H))
    return jnp.tanh(p["gate"].astype(F32)).astype(o.dtype) * o, (k, v)


# ---------------------------------------------------------------------------
# FFNs
# ---------------------------------------------------------------------------

def ffn_kind(cfg: ArchConfig) -> str:
    if cfg.name.startswith("gemma"):
        return "geglu"
    if cfg.family == "audio":
        return "gelu_mlp"
    return "swiglu"


def ffn_specs(cfg: ArchConfig) -> dict:
    D, Fdim = cfg.d_model, cfg.d_ff
    if ffn_kind(cfg) == "gelu_mlp":
        return {"w1": ParamSpec((D, Fdim), ("embed", "ffn")),
                "b1": ParamSpec((Fdim,), ("ffn",), "zeros"),
                "w2": ParamSpec((Fdim, D), ("ffn", "embed")),
                "b2": ParamSpec((D,), ("embed",), "zeros")}
    return {"w_gate": ParamSpec((D, Fdim), ("embed", "ffn")),
            "w_up": ParamSpec((D, Fdim), ("embed", "ffn")),
            "w_down": ParamSpec((Fdim, D), ("ffn", "embed"))}


def ffn_forward(cfg: ArchConfig, p: dict, x):
    dt = cfg.compute_dtype
    kind = ffn_kind(cfg)
    Fdim = cfg.d_ff
    if kind == "gelu_mlp":
        h = dense_proj(cfg, x, p["w1"], shard=("col", Fdim)) + p["b1"].astype(dt)
        h = jax.nn.gelu(h)
        return dense_proj(cfg, h, p["w2"], shard=("row", Fdim)) + p["b2"].astype(dt)
    g = dense_proj(cfg, x, p["w_gate"], shard=("col", Fdim))
    u = dense_proj(cfg, x, p["w_up"], shard=("col", Fdim))
    act = jax.nn.gelu(g, approximate=True) if kind == "geglu" else jax.nn.silu(g)
    return dense_proj(cfg, act * u, p["w_down"], shard=("row", Fdim))


# ---------------------------------------------------------------------------
# MoE FFN — capacity-factor top-k dispatch (Switch-style), SPMD-friendly:
# tokens grouped along the data axis, experts sharded along the model axis;
# the group->expert reshard is the MoE all-to-all.
# ---------------------------------------------------------------------------

def moe_specs(cfg: ArchConfig) -> dict:
    D, Fdim, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    return {
        "router": ParamSpec((D, E), ("embed", "experts"), "normal", jnp.float32),
        "w_gate": ParamSpec((E, D, Fdim), ("experts", "embed", "ffn")),
        "w_up": ParamSpec((E, D, Fdim), ("experts", "embed", "ffn")),
        "w_down": ParamSpec((E, Fdim, D), ("experts", "ffn", "embed")),
    }


def moe_capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.experts_per_token * cfg.capacity_factor
            / cfg.num_experts)
    return max(4, round_up(max(c, 1), 4))


def _moe_expert_block(xt, wk3, idx3, sel3, pos3, wg, wu, wd, *, E_l: int,
                      C: int, kk: int, dt, axis: str | None):
    """Gather-dispatch + SwiGLU experts + gather-combine.

    xt: [G,T,D] (replicated over the expert/model axis); wk3: [G,k,T] router
    weights; idx3: [G,E_l,C] token-id+1 per slot (0 = empty); sel3/pos3:
    [G,k,T] expert id / slot position per (choice, token); wg/wu/wd:
    [E_l, D, F] local expert shard.  Runs inside shard_map(axis) (manual
    expert axis, TPU) or plain (axis=None, E_l=E, CPU/auto).

    Gather-only formulation: batched scatters of [T,D] update blocks
    partition catastrophically under auto-SPMD (measured: 128 GiB u32
    all-gathers on qwen3 — see EXPERIMENTS.md §Perf); batched gathers with
    sharded index arrays stay local, and the combine gathers straight from
    the expert-sharded [G,E,C+1,D] outputs so the partitioner can use
    masked-gather + partial-sum instead of replicating the slot buffer."""
    manual = axis is not None
    base_e = (lax.axis_index(axis) * E_l) if axis else 0
    G, T, D = xt.shape
    gi = jnp.arange(G)[:, None]

    # dispatch: ein[g,e,c] = xt[g, idx3[g,e,c]-1] (slot 0 -> zero row).
    # All gathers/scatters are vmapped over G so it becomes an HLO operand
    # *batching* dim — indexing G explicitly puts it in the scatter index
    # space, which XLA's partitioner cannot shard (measured: full-batch f32
    # replication + 24 GiB all-gathers per layer; EXPERIMENTS.md §Perf).
    xt_pad = jnp.concatenate([jnp.zeros((G, 1, D), dt), xt.astype(dt)], axis=1)
    if not manual:  # fresh tensors lose the G(data) sharding: re-pin
        xt_pad = constrain(xt_pad, ("batch", None, "embed"))
    ein = jax.vmap(lambda xp, ix: xp[ix])(xt_pad, idx3)  # [G,E_l,C,D]
    if not manual:
        ein = constrain(ein, ("batch", "experts", None, "embed"))
    g = jnp.einsum("gecd,edf->gecf", ein, wg.astype(dt),
                   preferred_element_type=F32).astype(dt)
    u = jnp.einsum("gecd,edf->gecf", ein, wu.astype(dt),
                   preferred_element_type=F32).astype(dt)
    eout = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u, wd.astype(dt),
                      preferred_element_type=F32).astype(dt)
    if not manual:
        eout = constrain(eout, ("batch", "experts", None, "embed"))
    # combine: scatter-ADD each slot's output back to its token (idx3 is the
    # slot->token map; row 0 is the trash row for empty slots).  Wire cost is
    # one [G,T,D] partial-sum merge instead of replicating the full
    # [G,E*C,D] slot buffer (16x fewer bytes at kimi-k2 scale).  Two earlier
    # formulations measured worse — flat-slot gather from a replicated
    # buffer (AG-bound) and (expert,slot)-pair gather (XLA replicates
    # per-gather); see EXPERIMENTS.md §Perf.
    wslot = jnp.zeros((G, E_l * C + 1), F32)
    for j in range(kk):
        e_j, p_j = sel3[:, j], pos3[:, j]
        le = e_j - base_e
        valid = (le >= 0) & (le < E_l) & (p_j < C)
        lidx = jnp.where(valid, le * C + jnp.minimum(p_j, C - 1), E_l * C)
        wslot = jax.vmap(lambda w, ix, u: w.at[ix].add(u))(
            wslot, lidx, wk3[:, j].astype(F32))
    weighted = eout.reshape(G, E_l * C, D) * \
        wslot[:, : E_l * C, None].astype(dt)
    out_pad = jnp.zeros((G, T + 1, D), dt)
    if not manual:
        out_pad = constrain(out_pad, ("batch", None, "embed"))
    idx_flat = idx3.reshape(G, E_l * C)
    out = jax.vmap(lambda op, ix, up: op.at[ix].add(up))(
        out_pad, idx_flat, weighted)[:, 1:]
    if not manual:
        out = constrain(out, ("batch", None, "embed"))
    if axis:
        # f32 psum: the CPU AllReducePromotion pass check-fails on 16-bit
        # all-reduces with non-add combiners (compiler bug); TPU unaffected.
        out = lax.psum(out.astype(F32), axis).astype(dt)
    return out


def moe_forward(cfg: ArchConfig, p: dict, x):
    """x: [B,S,D] -> [B,S,D].  Returns (out, aux_loss)."""
    B, S, D = x.shape
    E, kk = cfg.num_experts, cfg.experts_per_token
    G = max(1, min(cfg.num_moe_groups, B * S))
    T = (B * S) // G
    C = moe_capacity(cfg, T)
    xt = constrain(x.reshape(G, T, D), ("batch", None, "embed"))

    logits = jnp.einsum("gtd,de->gte", xt.astype(F32), p["router"].astype(F32))
    probs = jax.nn.softmax(logits, -1)
    topw, topi = lax.top_k(probs, kk)  # [G,T,k]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # slot assignment with first-choice priority: exclusive cumsum over (k, t)
    dt = cfg.compute_dtype
    ohp = jax.nn.one_hot(topi.transpose(0, 2, 1).reshape(G, kk * T), E,
                         dtype=jnp.int32)  # [G,kT,E] priority-major
    ohp = constrain(ohp, ("batch", None, "experts"))
    pos_all = jnp.cumsum(ohp, axis=1) - ohp  # exclusive, [G,kT,E]
    chosen_pos = (pos_all * ohp).sum(-1)  # [G,kT] slot within chosen expert
    sel = topi.transpose(0, 2, 1).reshape(G, kk * T)

    gidx = jnp.where(chosen_pos < C, sel * C + chosen_pos, E * C)  # E*C=drop

    # invert (token -> slot) into (slot -> token): tiny int32 scatter; the
    # heavy data movement is then gather-only, O(T*k*D).  Both alternatives
    # were measured and rejected (EXPERIMENTS.md §Perf): the one-hot dispatch
    # einsum costs O(T*E*C*D) FLOPs (40x model flops at kimi-k2 scale) and
    # batched [T,D]-block scatters replicate catastrophically under
    # auto-SPMD (128 GiB u32 all-gathers on qwen3).
    tok1 = jnp.tile(jnp.arange(1, T + 1, dtype=jnp.int32)[None], (1, kk))
    tok_of_slot = constrain(jnp.zeros((G, E * C + 1), jnp.int32), ("batch", None))
    tok_of_slot = jax.vmap(lambda t, ix, u: t.at[ix].set(u, mode="drop"))(
        tok_of_slot, gidx, jnp.broadcast_to(tok1, (G, kk * T)))
    idx3 = constrain(tok_of_slot[:, : E * C].reshape(G, E, C),
                     ("batch", "experts", None))
    sel3 = sel.reshape(G, kk, T)
    pos3 = chosen_pos.reshape(G, kk, T)
    wk3 = topw.transpose(0, 2, 1)  # [G,k,T]

    dt = cfg.compute_dtype
    mesh = current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if cfg.moe_shard_map and mesh is not None and tp > 1 and E % tp == 0:
        from jax.sharding import PartitionSpec as P
        # ZeRO-3 boundary: explicitly all-gather the FSDP (data-axis) shards
        # of the expert weights *before* the manual region — a data-sharded
        # contraction inside shard_map would otherwise force a cross-data
        # psum per expert GEMM (and trips an XLA CPU promotion-pass bug on
        # the bf16 copy-combiner all-reduce it generates).
        wg_, wu_, wd_ = (constrain(p[k], ("experts", None, None))
                         for k in ("w_gate", "w_up", "w_down"))
        body = functools.partial(_moe_expert_block, E_l=E // tp, C=C, kk=kk,
                                 dt=dt, axis="model")
        fn = tp_shard_map(
            body, mesh,
            (P(), P(), P(None, "model", None), P(), P(), P("model"),
             P("model"), P("model")),
            P())
        out = fn(xt, wk3, idx3, sel3, pos3, wg_, wu_, wd_)
    else:
        out = _moe_expert_block(xt, wk3, idx3, sel3, pos3, p["w_gate"],
                                p["w_up"], p["w_down"], E_l=E, C=C, kk=kk,
                                dt=dt, axis=None)
    out = constrain(out, ("batch", None, "embed"))

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))  # [E]
    fe = ohp.reshape(G, kk, T, E).sum(1).astype(F32).mean(axis=(0, 1)) / kk
    aux = E * jnp.sum(me * fe)
    return out.reshape(B, S, D), aux
