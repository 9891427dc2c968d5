"""Logical-axis -> mesh partitioning rules (MaxText/t5x style).

Every parameter / activation / cache dimension carries a logical axis name
(see ``repro.models.params``); this module maps those onto the production
mesh with divisibility-checked fallback (a 16-way model axis cannot shard 8
KV heads -> replicate) and FSDP (ZeRO-3) sharding of params/optimizer over
the data axis.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.quant import QTensor

# NOTE: repro.models.params is imported lazily inside functions — model code
# imports `constrain` from this module, so a module-level import here would
# be circular.

# tensor-parallel rules: logical axis -> mesh axis
TP_RULES: dict[str, str] = {
    "vocab": "model",
    "ffn": "model",
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
}
# data-parallel rules for activations/inputs (pod-major batch)
BATCH_AXES = ("pod", "data")
# FSDP preference order: which logical axis to shard over `data`
FSDP_PREF = ("embed", "ffn", "vocab", "frontend", "lora", "qk")


import dataclasses as _dc


@_dc.dataclass(frozen=True)
class ShardingProfile:
    """Parallelism layout.  "2d" = TP over `model` + FSDP over `data`
    (default); "fsdp" = no tensor parallelism, batch and parameters sharded
    over BOTH axes (ZeRO-3 across all 256 chips) — the right layout when the
    per-chip batch stays >= 1 and TP's residual all-reduces dominate
    (see EXPERIMENTS.md §Perf I5)."""
    tp_rules: dict = _dc.field(default_factory=lambda: dict(TP_RULES))
    batch_axes: tuple = BATCH_AXES
    fsdp_axes: tuple = ("data",)


def profile_for(cfg) -> ShardingProfile:
    if getattr(cfg, "parallel_mode", "2d") == "fsdp":
        return ShardingProfile(tp_rules={},
                               batch_axes=("pod", "data", "model"),
                               fsdp_axes=("data", "model"))
    return ShardingProfile()


def _divisible(dim: int, size: int) -> bool:
    return dim % size == 0 and dim >= size


def resolve_pspec(spec, mesh: Mesh, *, fsdp: bool = False,
                  extra_rules: dict | None = None,
                  profile: "ShardingProfile | None" = None) -> P:
    profile = profile or _current_profile()
    rules = dict(profile.tp_rules)
    if extra_rules:
        rules.update(extra_rules)
    assigned: list = []
    used: set = set()
    for dim, ax in zip(spec.shape, spec.axes):
        entry = None
        if ax == "batch":
            # graded fallback: full batch axes, then drop leading axes
            bax = tuple(a for a in profile.batch_axes if a in mesh.shape)
            cands = [bax[i:] for i in range(len(bax))]
            for cand in cands:
                size = 1
                for a in cand:
                    size *= mesh.shape[a]
                if cand and not (used & set(cand)) and _divisible(dim, size):
                    entry = cand if len(cand) > 1 else cand[0]
                    used |= set(cand)
                    break
        elif ax in rules:
            m = rules[ax]
            if m and m in mesh.shape and m not in used and _divisible(dim, mesh.shape[m]):
                entry = m
                used.add(m)
        assigned.append(entry)
    fax = tuple(a for a in profile.fsdp_axes if a in mesh.shape and a not in used)
    if fsdp and fax:
        fsize = 1
        for a in fax:
            fsize *= mesh.shape[a]
        # prefer the canonical FSDP axes, then any unassigned divisible dim
        order = sorted(
            range(len(assigned)),
            key=lambda i: (FSDP_PREF.index(spec.axes[i])
                           if spec.axes[i] in FSDP_PREF else len(FSDP_PREF)),
        )
        for i in order:
            if assigned[i] is None and spec.axes[i] is not None \
                    and _divisible(spec.shape[i], fsize):
                assigned[i] = fax if len(fax) > 1 else fax[0]
                break
    return P(*assigned)


def tree_pspecs(spec_tree, mesh: Mesh, *, fsdp: bool = False,
                extra_rules: dict | None = None,
                profile: "ShardingProfile | None" = None):
    from repro.models.params import is_spec
    return jax.tree.map(
        lambda s: NamedSharding(mesh, resolve_pspec(s, mesh, fsdp=fsdp,
                                                    extra_rules=extra_rules,
                                                    profile=profile)),
        spec_tree, is_leaf=is_spec)


def moment_pspecs(param_pspec_tree):
    """Moments mirror param shardings; int8 QTensor scales drop the last dim."""
    def conv(ns: NamedSharding):
        return ns
    return jax.tree.map(conv, param_pspec_tree)


def qtensor_pspecs(param_ns: NamedSharding) -> QTensor:
    spec = param_ns.spec
    scale_spec = P(*(tuple(spec[:-1]) + (None,))) if len(spec) else P()
    return QTensor(param_ns, NamedSharding(param_ns.mesh, scale_spec))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def tp_size(mesh: Mesh | None = None) -> int:
    """Size of the tensor-parallel (`model`) axis; 1 when no mesh is active."""
    mesh = mesh if mesh is not None else _ACT_MESH.get()
    if mesh is None:
        return 1
    return dict(mesh.shape).get("model", 1)


def tp_shard_map(body, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` whose specs name only the tensor-parallel ``model``
    axis.  Used for Pallas kernels (which have no SPMD partitioning rules —
    each shard runs the unmodified kernel on its slice) and the MoE
    expert-parallel block.

    Every mesh axis is manual: Mosaic refuses a kernel left under the auto
    partitioner on any axis, even one of size 1, so the data axis cannot
    stay auto; the body sees the whole batch on every data replica.
    ``pallas_call`` declares no varying-mesh-axes type for its outputs, so
    the check that needs one (``check_vma``) is off."""
    return jax.shard_map(body, mesh=mesh, axis_names=set(mesh.axis_names),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def psum_bytes(closed) -> int:
    """Bytes per device that the ``psum`` all-reduces of a traced program
    carry (``jitted.trace(*args).jaxpr``): the size of each ``psum``'s
    result inside the manual regions, where shapes are per device, times
    the trip count of every ``scan`` around it.  For a dense model these
    are the f32 sums after the row-parallel GEMMs (``wo``, ``w_down``).
    A ``while`` body counts once and a ``cond`` its largest branch;
    collectives that XLA's partitioner adds outside ``shard_map`` are not
    in the jaxpr and are not counted."""
    def walk(jaxpr) -> int:
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "psum":
                total += sum(v.aval.size * v.aval.dtype.itemsize
                             for v in eqn.outvars)
                continue
            subs = []
            for val in eqn.params.values():
                for item in (val if isinstance(val, (tuple, list)) else [val]):
                    if hasattr(item, "consts") and hasattr(item, "jaxpr"):
                        subs.append(item.jaxpr)  # ClosedJaxpr
                    elif hasattr(item, "eqns"):
                        subs.append(item)        # open Jaxpr
            inner = [walk(s) for s in subs]
            if eqn.primitive.name == "cond":
                total += max(inner, default=0)
            elif eqn.primitive.name == "scan":
                total += int(eqn.params["length"]) * sum(inner)
            else:
                total += sum(inner)
        return total

    return walk(closed.jaxpr)


def batch_pspec(mesh: Mesh, batch_dim_divisor: int = 0) -> P:
    axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    return P(axes if len(axes) > 1 else (axes[0] if axes else None))


# ---------------------------------------------------------------------------
# Activation sharding constraints.  Without these, XLA's sharding propagation
# is free to replicate the batch across the data axis and turn the FSDP
# weight sharding into contraction-dim "tensor parallelism" — catastrophic
# (measured: 16x activation blow-up + TB-scale cross-data all-reduces on
# olmo-1b).  Model code calls ``constrain(x, logical_axes)`` at the residual
# stream and other anchor points; it is a no-op unless a mesh is active.
# ---------------------------------------------------------------------------

_ACT_MESH: contextvars.ContextVar = contextvars.ContextVar("act_mesh", default=None)
_ACT_PROFILE: contextvars.ContextVar = contextvars.ContextVar("act_profile",
                                                              default=None)


@contextlib.contextmanager
def activation_mesh(mesh: Mesh, profile: "ShardingProfile | None" = None):
    """Set while *tracing/lowering* (constraints are applied at trace time)."""
    tok = _ACT_MESH.set(mesh)
    tok2 = _ACT_PROFILE.set(profile)
    try:
        yield
    finally:
        _ACT_MESH.reset(tok)
        _ACT_PROFILE.reset(tok2)


def current_mesh() -> Mesh | None:
    return _ACT_MESH.get()


def _current_profile() -> "ShardingProfile":
    return _ACT_PROFILE.get() or ShardingProfile()


def constrain(x, axes: tuple):
    mesh = _ACT_MESH.get()
    if mesh is None or x is None:
        return x
    from repro.models.params import ParamSpec
    spec = resolve_pspec(ParamSpec(x.shape, axes), mesh, fsdp=False)
    # inside a shard_map manual region the ambient abstract mesh marks some
    # axes Manual; constraints there must target that mesh with the manual
    # axes dropped from the spec (they are already local)
    am = jax.sharding.get_abstract_mesh()
    manual = {n for n, t in zip(am.axis_names, am.axis_types)
              if t == jax.sharding.AxisType.Manual}
    if manual:
        entries = []
        for e in spec:
            if isinstance(e, tuple):
                kept = tuple(a for a in e if a not in manual)
                e = kept if len(kept) > 1 else (kept[0] if kept else None)
            elif e in manual:
                e = None
            entries.append(e)
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, P(*entries)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
