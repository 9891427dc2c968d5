"""Dry-run cell construction: (arch x shape x mesh) -> lowerable jit call.

A *cell* is one entry of the assigned 10 x 4 grid.  ``build_cell`` returns
the step function, ShapeDtypeStruct arguments (zero allocation — kimi-k2's
1T parameters stay imaginary) and the full in_shardings tree resolved from
the logical-axis rules.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch import sharding as SH
from repro.models import model as M
from repro.models.params import ParamSpec, shape_tree
from repro.serving.config import EngineConfig
from repro.training.optimizer import AdamWConfig
from repro.training.step import make_train_step, state_shapes


def prepare_arch(cfg: ArchConfig, mesh: Mesh) -> ArchConfig:
    """Specialize an arch config for a mesh: head padding for TP
    divisibility, MoE dispatch groups = DP degree."""
    tp = mesh.shape.get("model", 1)
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh.shape.get(a, 1)
    kw: dict = {"num_moe_groups": dp}
    if cfg.num_heads:
        kw["pad_heads_to"] = tp  # shard q-heads over the model axis
    new = cfg.with_(**kw)
    if (not new.use_mla) and new.num_heads and new.num_kv_heads \
            and new.padded_heads % new.num_kv_heads:
        new = new.with_(pad_heads_to=1)  # keep GQA grouping exact; replicate
    return new


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins (as ParamSpecs for axis metadata) for every
    model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    sp: dict = {}
    if shape.step == "decode":
        sp["tokens"] = ParamSpec((B, 1), ("batch", None), dtype=jnp.int32)
    elif cfg.audio_frontend:
        sp["frames"] = ParamSpec((B, S, cfg.frontend_dim), ("batch", None, None),
                                 dtype=cfg.compute_dtype)
    else:
        sp["tokens"] = ParamSpec((B, S), ("batch", None), dtype=jnp.int32)
    if shape.step == "train":
        sp["labels"] = ParamSpec((B, S), ("batch", None), dtype=jnp.int32)
    if cfg.vision_tokens and shape.step != "decode":
        sp["images"] = ParamSpec((B, cfg.vision_tokens, cfg.vision_dim),
                                 ("batch", None, None), dtype=cfg.compute_dtype)
    return sp


class Cell(NamedTuple):
    fn: Any  # callable to jit
    args: tuple  # ShapeDtypeStruct pytrees
    in_shardings: tuple
    donate: tuple
    cfg: ArchConfig


def _params_shardings(cfg, mesh, main_repeats):
    specs = M.param_specs(cfg, main_repeats)
    return SH.tree_pspecs(specs, mesh, fsdp=cfg.fsdp,
                          profile=SH.profile_for(cfg))


def _state_shardings(cfg, opt, mesh, main_repeats):
    from repro.training.step import TrainState
    p_ns = _params_shardings(cfg, mesh, main_repeats)
    if opt.moments_dtype == "int8":
        mom = jax.tree.map(SH.qtensor_pspecs, p_ns)
    else:
        mom = p_ns
    return TrainState(SH.replicated(mesh), p_ns, mom, mom)


def _batch_shardings(cfg, shape, mesh):
    sp = input_specs(cfg, shape)
    return SH.tree_pspecs(sp, mesh, fsdp=False, profile=SH.profile_for(cfg))


def _batch_shapes(cfg, shape):
    return shape_tree(input_specs(cfg, shape), cfg.compute_dtype)


def build_cell(cfg0: ArchConfig, shape: ShapeConfig, mesh: Mesh, *,
               opt: AdamWConfig | None = None,
               main_repeats: int | None = None,
               scan_layers: bool = True,
               attn_chunk: int = 0,
               accum_steps: int = 1,
               compress_pod: bool = False) -> Cell:
    opt = opt or AdamWConfig()
    cfg = prepare_arch(cfg0, mesh).with_(scan_layers=scan_layers)
    B, S = shape.global_batch, shape.seq_len

    if shape.step == "train":
        step = make_train_step(cfg, opt, attn_chunk=attn_chunk,
                               accum_steps=accum_steps,
                               main_repeats=main_repeats,
                               compress_pod=compress_pod, mesh=mesh)
        st = state_shapes(cfg, opt, main_repeats)
        bt = _batch_shapes(cfg, shape)
        in_sh = (_state_shardings(cfg, opt, mesh, main_repeats),
                 _batch_shardings(cfg, shape, mesh))
        return Cell(step, (st, bt), in_sh, (0,), cfg)

    if shape.step == "prefill":
        def fn(params, batch):
            return M.prefill(cfg, params, batch, attn_chunk=attn_chunk,
                             main_repeats=main_repeats)
        ps = M.param_shapes(cfg, main_repeats)
        bt = _batch_shapes(cfg, shape)
        in_sh = (_params_shardings(cfg, mesh, main_repeats),
                 _batch_shardings(cfg, shape, mesh))
        return Cell(fn, (ps, bt), in_sh, (), cfg)

    # decode: one token per slot over the paged cache, each slot holding
    # a full table of the serving engine's default pages
    page = EngineConfig.page_size
    npp = -(-S // page)

    def fn(params, caches, token, pos, pages):
        return M.decode_step(cfg, params, caches, token, pos, pages=pages,
                             main_repeats=main_repeats)
    ps = M.param_shapes(cfg, main_repeats)
    cs_specs = M.paged_cache_specs(cfg, B, B * npp + 1, page, main_repeats)
    cs = shape_tree(cs_specs, cfg.compute_dtype)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    pages = jax.ShapeDtypeStruct((B, npp), jnp.int32)
    in_sh = (_params_shardings(cfg, mesh, main_repeats),
             SH.tree_pspecs(cs_specs, mesh, fsdp=False,
                            profile=SH.profile_for(cfg)),
             NamedSharding(mesh, SH.batch_pspec(mesh) if B > 1 else P()),
             SH.replicated(mesh), SH.replicated(mesh))
    # token sharding: batch axis must divide B
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh.shape.get(a, 1)
    if B % dp:
        in_sh = (in_sh[0], in_sh[1], SH.replicated(mesh), *in_sh[3:])
    return Cell(fn, (ps, cs, tok, pos, pages), in_sh, (1,), cfg)
