"""Architecture configuration system.

Every assigned architecture is described by an :class:`ArchConfig`.  The config
is a *complete* description: the model zoo in ``repro.models`` builds parameter
trees and apply functions purely from it, the launcher derives shardings from
it, and the dry-run derives input specs from it.

Layer stacks are expressed as a repeating *pattern* of :class:`LayerSpec`s
(mixer kind + ffn kind).  ``build_stages`` factors the pattern into scan-able
stages (a group of layers scanned ``repeats`` times) so that 95-layer models
compile as a single small HLO while heterogeneous interleaves (Jamba 1:7,
Gemma-3 5:1) remain exact.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Sequence

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Layer pattern machinery
# ---------------------------------------------------------------------------

# mixer kinds: attn_global | attn_local | ssm | cross  (cross = self-attn layer
# followed by an image cross-attention sub-block, Llama-3.2-Vision style)
# ffn kinds:   dense | moe
@dataclass(frozen=True)
class LayerSpec:
    mixer: str
    ffn: str


@dataclass(frozen=True)
class Stage:
    """``repeats`` scanned iterations of a fixed ``group`` of layers."""

    group: tuple[LayerSpec, ...]
    repeats: int

    @property
    def num_layers(self) -> int:
        return len(self.group) * self.repeats


def _is_periodic(specs: Sequence[LayerSpec], p: int) -> bool:
    return all(specs[i] == specs[i % p] for i in range(len(specs)))


def build_stages(specs: Sequence[LayerSpec]) -> list[Stage]:
    """Factor a layer list into <=2 scan stages (main periodic prefix + tail)."""
    n = len(specs)
    if n == 0:
        return []
    for p in range(1, n + 1):
        n_full = n // p
        if n_full == 0:
            break
        prefix = specs[: n_full * p]
        if _is_periodic(prefix, p) and n_full * p >= max(p, n // 2):
            stages = [Stage(tuple(specs[:p]), n_full)]
            tail = specs[n_full * p :]
            if tail:
                stages.extend(build_stages(tail))
            return stages
    return [Stage(tuple(specs), 1)]


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    kind: str = "decoder"  # decoder | encoder

    # core transformer dims
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # attention pattern
    local_global_pattern: int = 0  # N locals per global; 0 = all global
    window_size: int = 0  # sliding window for local layers
    rope_theta: float = 10_000.0
    logit_softcap: float = 0.0
    use_qk_norm: bool = False

    # MLA (multi-head latent attention)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    moe_every: int = 1  # apply MoE FFN on every k-th layer (1 = all layers)
    capacity_factor: float = 1.0
    num_moe_groups: int = 1  # dispatch groups (= DP shards at scale)
    # expert-sharded dispatch under manual shard_map (slot buffers shard over
    # the model axis).  Engine turns it on by itself for a mesh whose model
    # axis divides num_experts (expert-parallel decode); one device keeps
    # the einsum dispatch.
    moe_shard_map: bool = False

    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    ssm_conv_width: int = 4
    ssm_every: int = 0  # hybrid: 1 attn per `ssm_every` layers (Jamba = 8); 0 = pure

    # VLM cross-attention
    cross_every: int = 0  # every k-th layer has an image cross-attn sub-block
    vision_tokens: int = 0
    vision_dim: int = 0

    # audio frontend stub
    audio_frontend: bool = False
    frontend_dim: int = 0

    # norm / misc
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm_nonparam
    tie_embeddings: bool = False

    # dtypes
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    # execution knobs (overridable by launcher / perf loop)
    kernel_mode: str = "reference"  # reference | pallas | interpret
    # w8a8: weights int8-quantized once at load (models.model.quantize_params),
    # activations quantized per-row on the fly, GEMMs through the packed int8
    # kernel with fused dequant — the paper's packed-data edge-inference mode
    quant: str = "none"  # none | w8a8
    remat_policy: str = "full"  # none | dots | full
    pad_heads_to: int = 1  # pad q heads to a multiple of this (TP divisibility)
    pad_vocab_to: int = 256
    fsdp: bool = True  # shard params/opt over the data axis
    parallel_mode: str = "2d"  # "2d" (TP x FSDP) | "fsdp" (ZeRO-3 only)
    use_torus_tp: bool = False  # ring-collective tensor parallelism (paper mode)
    scan_layers: bool = True

    # ---------------- derived helpers ----------------

    @property
    def padded_vocab(self) -> int:
        pv = self.pad_vocab_to
        return ((self.vocab_size + pv - 1) // pv) * pv

    @property
    def padded_heads(self) -> int:
        ph = self.pad_heads_to
        return ((self.num_heads + ph - 1) // ph) * ph

    @property
    def d_inner(self) -> int:  # SSD inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def layer_specs(self) -> list[LayerSpec]:
        specs = []
        for i in range(self.num_layers):
            # mixer
            if self.family in ("ssm",):
                mixer = "ssm"
            elif self.ssm_every:  # hybrid: one attn per ssm_every layers
                mixer = "attn_global" if (i % self.ssm_every) == self.ssm_every // 2 else "ssm"
            elif self.cross_every and ((i + 1) % self.cross_every == 0):
                mixer = "cross"
            elif self.local_global_pattern:
                p = self.local_global_pattern + 1
                mixer = "attn_global" if (i % p) == self.local_global_pattern else "attn_local"
            else:
                mixer = "attn_global"
            # ffn
            if self.num_experts and (i % self.moe_every == self.moe_every - 1):
                ffn = "moe"
            elif self.family == "ssm":
                ffn = "none"  # Mamba-2 blocks have no separate FFN
            else:
                ffn = "dense"
            specs.append(LayerSpec(mixer, ffn))
        return specs

    def stages(self, main_repeats: int | None = None) -> list[Stage]:
        """Scan stages; optionally override the main (largest) stage's repeats.

        ``main_repeats`` powers the roofline depth-extrapolation: compile at 1
        and 2 repeats of the main stage and extrapolate linearly — exact,
        because scan stages are homogeneous by construction.
        """
        stages = build_stages(self.layer_specs())
        if main_repeats is not None and stages:
            main = max(range(len(stages)), key=lambda i: stages[i].repeats)
            stages = [
                dataclasses.replace(s, repeats=main_repeats) if i == main else s
                for i, s in enumerate(stages)
            ]
        return stages

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (the assigned shape set)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    step: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def cell_skip_reason(cfg: ArchConfig, shape: ShapeConfig) -> str | None:
    """Return a reason string if this (arch x shape) cell is skipped, else None."""
    if cfg.kind == "encoder" and shape.step == "decode":
        return "encoder-only architecture has no autoregressive decode step"
    if shape.name == "long_500k":
        sub_quadratic = (
            cfg.family in ("ssm", "hybrid") or cfg.local_global_pattern > 0
        )
        if not sub_quadratic:
            return "pure full-attention arch: 524k dense-KV decode excluded per spec"
    return None
