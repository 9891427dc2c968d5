"""Config-driven model assembly: param specs, forward, prefill, decode, loss.

One code path serves all 10 assigned architectures; the :class:`ArchConfig`
selects mixers (attention global/local, MLA, SSD, cross-attn) and FFNs
(dense / MoE) per layer via the stage machinery, and the whole stack runs as
``lax.scan`` over homogeneous layer groups (with configurable rematerialization)
so 95-layer models lower to compact HLO.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, LayerSpec, Stage
from repro.core.quant import QTensor
from repro.launch.sharding import constrain
from repro.models import layers as L
from repro.models import ssd as S
from repro.models.params import (ParamSpec, init_params, is_spec, shape_tree,
                                  stack_tree)

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def _mixer_specs(cfg: ArchConfig, spec: LayerSpec) -> dict:
    if spec.mixer == "ssm":
        return S.ssd_specs(cfg)
    if cfg.use_mla:
        return L.mla_specs(cfg)
    if spec.mixer == "cross":
        return {"self": L.attn_specs(cfg), "cross": L.cross_attn_specs(cfg),
                "norm_cross": L.norm_specs(cfg)}
    return L.attn_specs(cfg)


def _layer_param_specs(cfg: ArchConfig, spec: LayerSpec) -> dict:
    d = {"norm1": L.norm_specs(cfg), "mixer": _mixer_specs(cfg, spec)}
    if spec.ffn == "dense":
        d["norm2"] = L.norm_specs(cfg)
        d["ffn"] = L.ffn_specs(cfg)
    elif spec.ffn == "moe":
        d["norm2"] = L.norm_specs(cfg)
        d["ffn"] = L.moe_specs(cfg)
    return d


def param_specs(cfg: ArchConfig, main_repeats: int | None = None) -> dict:
    D, Vp = cfg.d_model, cfg.padded_vocab
    tree: dict = {}
    if cfg.audio_frontend:
        tree["frontend_proj"] = ParamSpec((cfg.frontend_dim, D), ("frontend", "embed"))
    tree["embed"] = ParamSpec((Vp, D), ("vocab", "embed"), "normal")
    if cfg.vision_tokens:
        tree["vision_proj"] = ParamSpec((cfg.vision_dim, D), ("frontend", "embed"))
    stages = []
    for stage in cfg.stages(main_repeats):
        group = {str(i): _layer_param_specs(cfg, sp) for i, sp in enumerate(stage.group)}
        stages.append(stack_tree(group, stage.repeats))
    tree["stages"] = stages
    tree["final_norm"] = L.norm_specs(cfg)
    if not cfg.tie_embeddings:
        tree["lm_head"] = ParamSpec((D, Vp), ("embed", "vocab"), "normal")
    return tree


def init(cfg: ArchConfig, rng) -> dict:
    return init_params(param_specs(cfg), rng, cfg.param_dtype)


def param_shapes(cfg: ArchConfig, main_repeats: int | None = None):
    return shape_tree(param_specs(cfg, main_repeats), cfg.param_dtype)


# ---------------------------------------------------------------------------
# w8a8 weight quantization (one-time, at load)
# ---------------------------------------------------------------------------

# every weight consumed by ``layers.dense_proj``; anything else (norm scales,
# embeddings, RoPE-free SSM params, MoE expert tensors — batched einsum path,
# MLA's wq_b/wkv_b — needed in float for absorbed decode) stays float
_QUANT_NAMES = frozenset({"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                          "w1", "w2", "wq_a", "wkv_a", "lm_head"})


def _quantize_weight(w, red_axes: tuple) -> QTensor:
    """Symmetric int8 over ``red_axes`` (the contraction dims): per-output-
    channel scales, broadcastable against the original weight shape."""
    wf = w.astype(F32)
    amax = jnp.max(jnp.abs(wf), axis=red_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale.astype(F32))


def quantize_params(cfg: ArchConfig, params: dict) -> dict:
    """Quantize every GEMM weight to int8 once at load (``quant="w8a8"``).

    Returns a params tree where each ``dense_proj`` weight is a ``QTensor``
    (int8 values + per-output-channel f32 scales, stacked-layer leading axis
    preserved so ``lax.scan`` slices it like any other param); activations
    are quantized per-row on the fly inside ``cgra_gemm_w8a8``.  Idempotent —
    already-quantized leaves pass through.  Inference-only: the int8 tree is
    not differentiable.
    """
    def walk(tree, stacked: bool):
        if not isinstance(tree, dict):
            return tree
        if "router" in tree:  # MoE expert weights stay on the einsum path
            return tree
        out = {}
        for name, v in tree.items():
            if isinstance(v, dict):
                out[name] = walk(v, stacked)
            elif (name in _QUANT_NAMES and not isinstance(v, QTensor)
                  and getattr(v, "ndim", 0) >= 2):
                s = 1 if stacked else 0  # skip the scanned layers axis
                red = tuple(range(s, v.ndim - 1)) if name == "wo" else (s,)
                out[name] = _quantize_weight(v, red)
            else:
                out[name] = v
        return out

    new = dict(params)
    new["stages"] = [walk(st, True) for st in params["stages"]]
    if "lm_head" in params and not isinstance(params["lm_head"], QTensor):
        new["lm_head"] = _quantize_weight(params["lm_head"], (0,))
    if cfg.tie_embeddings and "lm_head_q" not in params:
        # tied head: the embedding stays float (it is a gather table), but
        # the head GEMM gets its own int8 copy of embed.T (1/4 the bytes)
        new["lm_head_q"] = _quantize_weight(params["embed"].T, (0,))
    return new


def shard_params(cfg: ArchConfig, params: dict, mesh, *, fsdp: bool = False):
    """Place a params tree on ``mesh`` with the logical-axis TP rules
    (heads/ffn/vocab/experts → ``model``, divisibility fallback intact).

    Handles the two ways a serving params tree deviates from ``param_specs``:
    ``QTensor`` leaves (w8a8) are placed *replicated* — sharding the int8
    GEMM's contraction dim would re-quantize activations per shard and break
    single-device numerics parity (DESIGN.md §9) — and the tied-head extra
    ``lm_head_q`` key gets the float head's ("embed", "vocab") spec.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.sharding import resolve_pspec
    from repro.models.params import is_spec
    specs = param_specs(cfg)
    if "lm_head_q" in params:
        specs = dict(specs, lm_head_q=ParamSpec((cfg.d_model, cfg.padded_vocab),
                                                ("embed", "vocab")))
    repl = NamedSharding(mesh, PartitionSpec())

    def place(spec, val):
        if isinstance(val, QTensor):
            return QTensor(jax.device_put(val.q, repl),
                           jax.device_put(val.scale, repl))
        ns = NamedSharding(mesh, resolve_pspec(spec, mesh, fsdp=fsdp))
        return jax.device_put(val, ns)

    return jax.tree.map(place, specs, params, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _layer_cache_specs(cfg: ArchConfig, spec: LayerSpec, batch: int, seq: int):
    if spec.mixer == "ssm":
        return S.ssd_cache_specs(cfg, batch)
    if cfg.use_mla:
        return L.mla_cache_specs(cfg, batch, seq)
    if spec.mixer == "cross":
        c = L.attn_cache_specs(cfg, batch, seq)
        K, dh, T = cfg.num_kv_heads, cfg.head_dim, cfg.vision_tokens
        c["ck"] = ParamSpec((batch, T, K, dh), ("batch", None, "kv_heads", "qk"), "zeros")
        c["cv"] = ParamSpec((batch, T, K, dh), ("batch", None, "kv_heads", "qk"), "zeros")
        return c
    return L.attn_cache_specs(cfg, batch, seq)


def _cache_specs(cfg: ArchConfig, batch: int, seq: int,
                 main_repeats: int | None = None) -> list:
    """Per-slot template of the decode-cache tree, ``[batch, seq, ...]``
    leaves stacked over each stage's layers, that :func:`paged_cache_specs`
    rewrites into page pools."""
    out = []
    for stage in cfg.stages(main_repeats):
        group = {str(i): _layer_cache_specs(cfg, sp, batch, seq)
                 for i, sp in enumerate(stage.group)}
        out.append(stack_tree(group, stage.repeats))
    return out


def paged_cache_specs(cfg: ArchConfig, max_batch: int, n_pages: int,
                      page_size: int, main_repeats: int | None = None) -> list:
    """The decode-cache spec tree: every ``kv_seq`` leaf is a page *pool*
    shared across sequences (per-sequence page tables map logical rows to
    pool pages; page 0 is the engine's reserved trash page).  Pools are
    head-major, ``[n_pages, K, page_size, d]`` for attention KV, so one
    Pallas block is one head's ``[page_size, d]`` tile; MLA's headless
    latent pool is ``[n_pages, page_size, d]``.  Sliding-window layers get
    full-size pages like global ones and window via the decode validity
    bound.  Leaves without a ``kv_seq`` axis (SSM state, cross-attn image
    KV) stay slot-indexed ``[max_batch, ...]``."""
    specs = _cache_specs(cfg, max_batch, page_size, main_repeats)

    def to_pool(spec):
        if "kv_seq" not in spec.axes:
            return spec
        axes, shape = list(spec.axes), list(spec.shape)
        b, s = axes.index("batch"), axes.index("kv_seq")
        axes[b] = None  # the pool's page axis is not a batch axis
        shape[b] = n_pages
        if "kv_heads" in axes:  # [.., kv_seq, kv_heads, d] -> head-major
            h = axes.index("kv_heads")
            axes.insert(s, axes.pop(h))
            shape.insert(s, shape.pop(h))
        return ParamSpec(tuple(shape), tuple(axes), "zeros", spec.dtype)

    return jax.tree.map(to_pool, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def init_paged_cache(cfg: ArchConfig, max_batch: int, n_pages: int,
                     page_size: int):
    tree = paged_cache_specs(cfg, max_batch, n_pages, page_size)
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype or cfg.compute_dtype),
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def write_prefill(cfg: ArchConfig, caches, small, table, slot):
    """Write one sequence's whole-prompt prefill into the paged cache tree.

    ``small`` is the cache tree :func:`prefill` returns for a batch of one
    (``n`` prompt rows); ``table``: [npp] int32, the sequence's page table;
    ``slot``: its batch row.  ``kv_seq`` leaves write their ``n`` rows to
    logical rows ``[0, n)`` through the table; stateful leaves (SSM state,
    cross-attention image KV) overwrite batch row ``slot``."""
    specs = paged_cache_specs(cfg, 1, 1, 1)  # leaf formats only

    def w(spec, pool, sm):
        rows = sm[:, 0].astype(pool.dtype)  # [R, n, (K,) d]
        if "kv_seq" not in spec.axes:
            return pool.at[:, slot].set(rows)
        ps = pool.shape[spec.axes.index("kv_seq")]
        j = jnp.arange(rows.shape[1], dtype=jnp.int32)
        page, off = table[j // ps], j % ps
        if "kv_heads" in spec.axes:  # head-major pool [R, P, K, ps, d]
            return pool.at[:, page, :, off].set(jnp.moveaxis(rows, 1, 0))
        return pool.at[:, page, off].set(rows)  # MLA pool [R, P, ps, d]

    return jax.tree.map(w, specs, caches, small, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _apply_layer(cfg: ArchConfig, spec: LayerSpec, p: dict, x, *, positions,
                 img, mode: str, cache=None, pos=None, pages=None,
                 attn_chunk: int = 0, chunk_len=None, layer=None):
    """Returns (x, new_cache, aux).

    decode: ``cache`` is the layer's paged cache — the head-major attention
    pools stacked over the stage's layers, this one at the int32 scalar
    ``layer`` (see :func:`_carried`) — and ``pages`` the [B, npp] page
    tables.  prefill: no cache in; the layer returns the prompt's full KV.
    chunk (chunked prefill): ``cache`` is the layer's page *pools*,
    ``pages`` the [B, npp] tables, ``chunk_len`` the valid rows in the
    chunk buffer — only prefix-decomposable mixers (pure attention) support
    it; SSM/MLA/cross raise.  The stacked pools come back updated in
    place."""
    aux = jnp.zeros((), F32)
    h = L.apply_norm(cfg, p["norm1"], x)
    new_cache = None
    local = spec.mixer == "attn_local"
    if spec.mixer == "ssm":
        if mode == "chunk":
            raise NotImplementedError("chunked prefill requires a prefix-"
                                      "decomposable mixer; SSM state is not")
        if mode == "decode":
            m, new_cache = S.ssd_decode(cfg, p["mixer"], cache, h)
        elif mode == "prefill":
            m, new_cache = S.ssd_forward(cfg, p["mixer"], h, return_cache=True)
        else:
            m = S.ssd_forward(cfg, p["mixer"], h)
    elif cfg.use_mla:
        if mode == "chunk":
            raise NotImplementedError("chunked prefill over the paged past "
                                      "does not support MLA's fused cache")
        if mode == "decode":
            m, new_cache = L.mla_decode(cfg, p["mixer"], cache, h, pos,
                                        pages=pages)
        elif mode == "prefill":
            m, new_cache = L.mla_prefill(cfg, p["mixer"], h, positions, attn_chunk)
        else:
            m = L.mla_forward(cfg, p["mixer"], h, positions, attn_chunk)
    elif spec.mixer == "cross":
        mp = p["mixer"]
        if mode == "chunk":
            raise NotImplementedError("chunked prefill does not support "
                                      "cross-attention image KV")
        if mode == "decode":
            m, sc = L.attn_decode(cfg, mp["self"], {"k": cache["k"], "v": cache["v"]},
                                  h, pos, local=False, pages=pages,
                                  layer=layer)
        elif mode == "prefill":
            m, sc = L.attn_prefill(cfg, mp["self"], h, positions, local=False,
                                   attn_chunk=attn_chunk)
        else:
            m = L.attn_forward(cfg, mp["self"], h, positions, local=False,
                               attn_chunk=attn_chunk)
            sc = None
        x = x + m
        hc = L.apply_norm(cfg, mp["norm_cross"], x)
        img_kv = (cache["ck"], cache["cv"]) if mode == "decode" else None
        mc, (ck, cv) = L.cross_attn(cfg, mp["cross"], hc, img, img_kv)
        if mode in ("decode", "prefill"):
            new_cache = dict(sc, ck=ck, cv=cv)
        m = mc  # residual added below
    else:
        if mode == "decode":
            m, new_cache = L.attn_decode(cfg, p["mixer"], cache, h, pos,
                                         local=local, pages=pages,
                                         layer=layer)
        elif mode == "chunk":
            m, new_cache = L.attn_chunk_prefill(cfg, p["mixer"], cache, h,
                                                positions, local=local,
                                                pages=pages,
                                                chunk_len=chunk_len,
                                                layer=layer)
        elif mode == "prefill":
            m, new_cache = L.attn_prefill(cfg, p["mixer"], h, positions, local=local,
                                          attn_chunk=attn_chunk)
        else:
            m = L.attn_forward(cfg, p["mixer"], h, positions, local=local,
                               attn_chunk=attn_chunk)
    x = x + m
    if spec.ffn != "none":
        h = L.apply_norm(cfg, p["norm2"], x)
        if spec.ffn == "moe":
            f, aux = L.moe_forward(cfg, p["ffn"], h)
        else:
            f = L.ffn_forward(cfg, p["ffn"], h)
        x = x + f
    return x, new_cache, aux


def _remat(cfg: ArchConfig, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=pol)
    if cfg.remat_policy == "dots_nb":
        # save weight-activation GEMM outputs, recompute batched einsums
        # (attention scores) — the memory/recompute sweet spot at depth
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # "full": save nothing


def _carried(cfg: ArchConfig, spec: LayerSpec, mode: str) -> set:
    """Keys of a layer's cache that stay one stacked buffer ``[L, ...]``
    through the stage's layer loop: the head-major attention page pools
    (``kv_seq`` and ``kv_heads`` axes) of a decode or chunk step.  Each
    layer writes its rows into the stack in place and the attention kernels
    read it at the layer's index, so no layer's pool is sliced out or
    stacked back.  Every other leaf (MLA's latent pool, SSM state,
    cross-attention image KV) is sliced per layer."""
    if mode not in ("decode", "chunk"):
        return set()
    return {k for k, s in _layer_cache_specs(cfg, spec, 1, 1).items()
            if "kv_seq" in s.axes and "kv_heads" in s.axes}


def _apply_stage(cfg: ArchConfig, stage: Stage, sp, x, *, positions, img,
                 mode: str, caches=None, pos=None, pages=None,
                 attn_chunk: int = 0, chunk_len=None, aux0=None):
    """Scan `stage.repeats` iterations of the layer group.  Stacked page
    pools (:func:`_carried`) ride in the carry, indexed by the layer; every
    other cache leaf is sliced per layer (``xs``) and stacked back (``ys``)."""
    group = stage.group
    keep = {str(gi): _carried(cfg, spec, mode)
            for gi, spec in enumerate(group)}
    stacks, sliced = {}, caches
    if caches is not None:
        stacks = {g: {k: c[k] for k in keep[g]} for g, c in caches.items()}
        sliced = {g: {k: v for k, v in c.items() if k not in keep[g]}
                  for g, c in caches.items()}

    def body(carry, xs):
        xc, aux, stacks = carry
        xc = constrain(xc, ("batch", "seq", "embed"))  # pin the residual stream
        lp, lc, r = xs
        new_caches, new_stacks = {}, dict(stacks)
        for gi, spec in enumerate(group):
            g = str(gi)
            c_in = None if lc is None else dict(lc[g], **stacks[g])
            xc, nc, a = _apply_layer(cfg, spec, lp[g], xc,
                                     positions=positions, img=img, mode=mode,
                                     cache=c_in, pos=pos, pages=pages,
                                     attn_chunk=attn_chunk,
                                     chunk_len=chunk_len, layer=r)
            if nc is not None:
                new_caches[g] = {k: v for k, v in nc.items()
                                 if k not in keep[g]}
                if g in stacks:
                    new_stacks[g] = {k: nc[k] for k in keep[g]}
            aux = aux + a
        ys = new_caches if new_caches else None
        return (xc, aux, new_stacks), ys

    if mode == "train":
        body = _remat(cfg, body)
    xs = (sp, sliced, jnp.arange(stage.repeats, dtype=jnp.int32))
    if cfg.scan_layers:
        (x, aux, stacks), ys = lax.scan(body, (x, aux0, stacks), xs)
    else:
        # unrolled path: identical math, no `while` in HLO — used by the
        # roofline cost compiles, where XLA's cost analysis counts a scan
        # body only once.
        aux = aux0
        ys_list = []
        for r in range(stage.repeats):
            xs_r = jax.tree.map(lambda a: a[r], xs)
            (x, aux, stacks), ys_r = body((x, aux, stacks), xs_r)
            ys_list.append(ys_r)
        ys = None
        if ys_list and ys_list[0] is not None:
            ys = jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
    if ys is not None:
        ys = {g: dict(c, **stacks.get(g, {})) for g, c in ys.items()}
    return x, aux, ys


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ArchConfig, params, tokens):
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype)
    return x


def embed_inputs(cfg: ArchConfig, params, batch: dict):
    if cfg.audio_frontend:
        return jnp.einsum("bsf,fd->bsd", batch["frames"].astype(cfg.compute_dtype),
                          params["frontend_proj"].astype(cfg.compute_dtype),
                          preferred_element_type=jnp.float32
                          ).astype(cfg.compute_dtype)
    return embed_tokens(cfg, params, batch["tokens"])


def project_images(cfg: ArchConfig, params, batch: dict):
    if not cfg.vision_tokens or "images" not in batch:
        return None
    return jnp.einsum("btf,fd->btd", batch["images"].astype(cfg.compute_dtype),
                      params["vision_proj"].astype(cfg.compute_dtype),
                      preferred_element_type=jnp.float32
                      ).astype(cfg.compute_dtype)


def lm_logits(cfg: ArchConfig, params, hidden):
    if cfg.tie_embeddings:
        head = params.get("lm_head_q", None)  # w8a8: int8 copy of embed.T
        head = params["embed"].T if head is None else head
    else:
        head = params["lm_head"]
    # f32 store: the GEMM epilogue's f32 accumulator reaches the sampler /
    # loss untouched instead of round-tripping through the compute dtype
    # (bf16 logits quantize argmax ties and top-k tails — analysis rule J006)
    logits = L.dense_proj(cfg, hidden, head, out_dtype=jnp.float32,
                          shard=("col", cfg.padded_vocab))
    return constrain(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward_hidden(cfg: ArchConfig, params, batch: dict, *, mode="train",
                   caches=None, pos=None, pages=None, past_len=0,
                   attn_chunk: int = 0, chunk_len=None,
                   main_repeats: int | None = None):
    """Run the stack; returns (hidden, aux_loss, new_caches_per_stage).

    decode: ``caches`` is the per-stage paged cache tree, ``pages`` the
    [B, npp] int32 page tables the attention pools are indirected through.
    prefill: no cache in; the stack returns the prompt's full KV.  chunk
    (chunked prefill): ``caches`` is the paged pool tree, ``pages`` the
    tables, ``past_len`` (traced scalar ok) the rows already prefilled,
    ``chunk_len`` the valid rows in the fixed-size chunk buffer.
    """
    x = embed_inputs(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed"))
    img = project_images(cfg, params, batch)
    seqlen = x.shape[1]
    if mode == "decode":
        positions = None
    else:
        positions = jnp.arange(seqlen, dtype=jnp.int32) + \
            jnp.asarray(past_len, jnp.int32)
    aux = jnp.zeros((), F32)
    new_caches = []
    for si, stage in enumerate(cfg.stages(main_repeats)):
        c = None if caches is None else caches[si]
        x, aux, ys = _apply_stage(cfg, stage, params["stages"][si], x,
                                  positions=positions, img=img, mode=mode,
                                  caches=c, pos=pos, pages=pages,
                                  attn_chunk=attn_chunk,
                                  chunk_len=chunk_len, aux0=aux)
        new_caches.append(ys)
    x = L.apply_norm(cfg, params["final_norm"], x)
    return x, aux, (new_caches if mode in ("prefill", "decode", "chunk")
                    else None)


def cross_entropy(cfg: ArchConfig, logits, labels):
    """Masked CE over the padded vocab.  logits: [B,S,Vp] (any float dtype)."""
    lf = logits.astype(F32)
    if cfg.padded_vocab != cfg.vocab_size:
        col = jnp.arange(cfg.padded_vocab)
        lf = jnp.where(col[None, None, :] < cfg.vocab_size, lf, L.NEG_INF)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def loss_fn(cfg: ArchConfig, params, batch: dict, *, attn_chunk: int = 0,
            main_repeats: int | None = None):
    hidden, aux, _ = forward_hidden(cfg, params, batch, mode="train",
                                    attn_chunk=attn_chunk,
                                    main_repeats=main_repeats)
    logits = lm_logits(cfg, params, hidden)
    ce = cross_entropy(cfg, logits, batch["labels"])
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


def prefill(cfg: ArchConfig, params, batch: dict, *, attn_chunk: int = 0,
            main_repeats: int | None = None):
    """Whole-prompt prefill.  Returns (last-token logits, caches): the
    prompt's KV rows (every row, sliding-window layers included) and
    stateful leaves, per stage, for :func:`write_prefill`."""
    hidden, _, caches = forward_hidden(cfg, params, batch, mode="prefill",
                                       attn_chunk=attn_chunk,
                                       main_repeats=main_repeats)
    logits = lm_logits(cfg, params, hidden[:, -1:])
    return logits, caches


def decode_step(cfg: ArchConfig, params, caches, token, pos, *, pages,
                main_repeats: int | None = None):
    """One-token decode over the paged cache.  token: [B,1] int32; pos:
    scalar int32 (all slots in lock-step) or [B] int32 (every sequence at
    its own offset, as driven by the continuous-batching engine); pages:
    [B, npp] int32 page tables.  The new row is written through the table
    and attention follows it (see ``layers.attn_decode``)."""
    batch = {"tokens": token}
    hidden, _, new_caches = forward_hidden(cfg, params, batch, mode="decode",
                                           caches=caches, pos=pos, pages=pages,
                                           main_repeats=main_repeats)
    logits = lm_logits(cfg, params, hidden)
    return logits, new_caches


def chunk_step(cfg: ArchConfig, params, caches, tokens, pages, past_len,
               chunk_len, *, main_repeats: int | None = None):
    """One chunked-prefill step: run a fixed-size prompt chunk through the
    paged cache.  tokens: [B, C] int32 chunk buffer (``chunk_len`` valid
    rows, rest padding); pages: [B, npp] page tables; ``past_len`` rows of
    this prompt are already in the pages (traced scalar ok).  The chunk's KV
    is written straight through the page table — no dense gather of the
    past — and the chunk attends over logical rows
    ``[0, past_len + chunk_len)``.  Returns (last-valid-row logits
    [B, 1, V], caches); the logits only mean anything when this chunk
    finishes the prompt."""
    batch = {"tokens": tokens}
    hidden, _, new_caches = forward_hidden(cfg, params, batch, mode="chunk",
                                           caches=caches, pages=pages,
                                           past_len=past_len,
                                           chunk_len=chunk_len,
                                           main_repeats=main_repeats)
    last = lax.dynamic_slice_in_dim(
        hidden, jnp.asarray(chunk_len, jnp.int32) - 1, 1, axis=1)
    logits = lm_logits(cfg, params, last)
    return logits, new_caches


def mixed_step(cfg: ArchConfig, params, caches, chunk_tokens, chunk_pages,
               chunk_past_len, chunk_len, dec_token, dec_pos, dec_pages, *,
               main_repeats: int | None = None):
    """The unified mixed step: one prompt chunk plus one decode token per
    slot, through shared layer application in a single compiled call.

    The chunk pass runs first (its KV lands in its own pages), then the
    decode pass runs over the updated pools — the two touch disjoint pages
    (a slot is either prefilling or decoding), so ordering is a dataflow
    convenience, not a semantic one.  Freeze a decode slot by pointing its
    ``dec_pages`` row at the trash page and ignoring its logits.  Returns
    (chunk_logits [Bc,1,V], dec_logits [B,1,V], caches)."""
    chunk_logits, caches = chunk_step(cfg, params, caches, chunk_tokens,
                                      chunk_pages, chunk_past_len, chunk_len,
                                      main_repeats=main_repeats)
    dec_logits, caches = decode_step(cfg, params, caches, dec_token, dec_pos,
                                     pages=dec_pages,
                                     main_repeats=main_repeats)
    return chunk_logits, dec_logits, caches
