"""Public serving configuration surface: ``EngineConfig`` + ``CacheSpec``.

``EngineConfig`` is the one frozen object that fully determines an engine's
compiled shapes and memory: previous PRs accreted these as loose ``Engine``
kwargs (``max_slots=``, ``prefill_bucket=``, ``kernel_mode=``, ...); the old
spelling still works through a ``DeprecationWarning`` shim in ``Engine``.

``CacheSpec`` describes the engine's paged KV-cache geometry (page size,
pool size) and is derived from the config via ``EngineConfig.cache_spec()``.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core import round_up


@dataclass(frozen=True)
class CacheSpec:
    """Geometry of the serving engine's paged KV cache.

    ``n_pages`` pages of ``page_size`` rows each (page 0 is the engine's
    reserved trash page), page tables of width ``pages_per_seq`` pages.
    ``max_rows`` is the usable KV row budget — the number every
    fixed-slot-vs-paged capacity comparison is made at.
    """
    page_size: int = 64
    n_pages: int = 0
    max_len: int = 512

    @property
    def pages_per_seq(self) -> int:
        return -(-self.max_len // self.page_size)

    @property
    def max_rows(self) -> int:
        """Usable KV rows (the trash page is bookkeeping, not capacity)."""
        return (self.n_pages - 1) * self.page_size


@dataclass(frozen=True)
class MeshSpec:
    """Serving mesh geometry: ``data`` replicas × ``model`` tensor/expert-
    parallel shards, built over the first ``data * model`` jax devices (a
    submesh — the platform may have more; see
    ``launch.mesh.make_device_mesh``).  Parse the CLI spelling with
    ``MeshSpec.parse("2x4")`` (``"4"`` alone means model-parallel only)."""
    data: int = 1
    model: int = 1

    def __post_init__(self):
        if self.data < 1 or self.model < 1:
            raise ValueError(f"mesh axes must be >= 1, got "
                             f"data={self.data} model={self.model}")

    @property
    def size(self) -> int:
        return self.data * self.model

    @classmethod
    def parse(cls, s: "str | MeshSpec") -> "MeshSpec":
        if isinstance(s, MeshSpec):
            return s
        parts = str(s).lower().replace("×", "x").split("x")
        try:
            if len(parts) == 1:
                return cls(1, int(parts[0]))
            if len(parts) == 2:
                return cls(int(parts[0]), int(parts[1]))
        except ValueError:
            pass
        raise ValueError(f"mesh spec {s!r}: expected 'DxM' (e.g. '1x8') or "
                         f"a bare model-parallel degree (e.g. '8')")

    def build(self):
        """The jax Mesh (imports jax; config construction itself does not)."""
        from repro.launch.mesh import make_device_mesh
        return make_device_mesh((self.data, self.model), ("data", "model"))


@dataclass(frozen=True)
class EngineConfig:
    """Everything the serving engine compiles and allocates against.

    page_size:     KV rows per page (multiple of 8 — TPU sublane alignment)
    n_pages:       page-pool size, incl. the reserved trash page; ``None``
                   derives ``max_batch * ceil(max_len / page_size) + 1`` (the
                   fixed-slot-equivalent budget, so legacy configs keep their
                   old capacity)
    max_batch:     concurrent sequences (the decode batch dimension)
    max_len:       per-sequence row cap; admission requires
                   ``len(prompt) + max_new <= max_len`` (exact — paging has
                   no pad rows to budget for)
    prefix_cache:  share KV pages between requests with a common prompt
                   prefix (radix tree + refcounted copy-on-write); auto-
                   disabled for architectures with SSM/cross-attention
                   mixers, whose prefill is not prefix-decomposable
    decode_chunk:  scan steps per compiled decode call
    chunk_tokens:  chunked-prefill budget — at most this many prompt tokens
                   per engine tick, run *together with* one decode step per
                   in-flight sequence in a single compiled mixed step, so
                   long prompts stream through without stalling decodes.
                   ``None`` (default) prefills each prompt in one
                   whole-suffix chunk (still through the mixed step on
                   prefix-decomposable models — one compiled variant per
                   power-of-two bucket, not per prompt length)
    eos_id:        optional stop token (checked inside the scan)
    max_queue:     admission-control queue bound; past it ``submit`` finishes
                   the request immediately as ``FinishReason.REJECTED`` with
                   a ``retry_after_s`` backpressure hint (never a silent
                   drop, never an unbounded queue)
    deadline_s:    default per-request deadline (seconds from submission,
                   spanning queueing and execution); requests past it retire
                   ``FinishReason.DEADLINE``.  ``None`` (default) means no
                   deadline; ``submit(deadline_s=...)`` overrides per request
    preemption:    page-pressure policy.  ``"off"`` (default): admission
                   reserves each request's full page need up front and the
                   pool can never exhaust mid-decode.  ``"recompute"``:
                   admission reserves only the prompt's pages, decode rows
                   grow lazily, and on exhaustion the scheduler preempts the
                   lowest-priority decoding slot (fewest tokens generated,
                   ties by latest arrival), frees its pages and requeues it —
                   its generated tokens recompute via normal chunked prefill
                   on re-admission, greedy outputs bit-identical to the
                   never-preempted run.  ``"drop"``: same victim policy, but
                   the victim retires ``FinishReason.PREEMPTED`` with its
                   partial output (load shedding)
    kernel_mode:   override ``cfg.kernel_mode`` (reference|interpret|pallas)
    quant:         override ``cfg.quant`` ("w8a8" quantizes weights at init)
    mesh:          optional ``MeshSpec`` — place params/caches with
                   ``NamedSharding`` over a ``(data, model)`` device mesh and
                   compile every executable under it (tensor-parallel dense
                   layers, KV pools sharded over KV heads, expert-parallel
                   MoE).  ``None`` (default) keeps the single-device path
                   byte-for-byte unchanged.  Accepts a ``MeshSpec`` or the
                   CLI string spelling (``"1x8"``/``"8"``)
    """
    page_size: int = 64
    n_pages: int | None = None
    max_batch: int = 8
    max_len: int = 512
    prefix_cache: bool = True
    decode_chunk: int = 8
    chunk_tokens: int | None = None
    eos_id: int | None = None
    max_queue: int = 1024
    deadline_s: float | None = None
    preemption: str = "off"
    kernel_mode: str | None = None
    quant: str | None = None
    mesh: MeshSpec | str | None = None

    def __post_init__(self):
        if self.page_size < 8 or self.page_size % 8:
            raise ValueError(f"page_size={self.page_size} must be a positive "
                             f"multiple of 8 (TPU sublane alignment)")
        if self.chunk_tokens is not None and self.chunk_tokens < 1:
            raise ValueError(f"chunk_tokens={self.chunk_tokens} must be >= 1 "
                             f"(or None for whole-suffix prefill)")
        if self.preemption not in ("off", "recompute", "drop"):
            raise ValueError(f"preemption={self.preemption!r} must be one of "
                             f"'off', 'recompute', 'drop'")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s={self.deadline_s} must be > 0 "
                             f"(or None for no deadline)")
        if self.max_len % self.page_size:
            object.__setattr__(self, "max_len",
                               round_up(self.max_len, self.page_size))
        if self.n_pages is None:
            per_seq = self.max_len // self.page_size
            object.__setattr__(self, "n_pages",
                               self.max_batch * per_seq + 1)
        if self.n_pages < 2:
            raise ValueError("n_pages must be >= 2 (one usable page plus the "
                             "reserved trash page)")
        if self.mesh is not None and not isinstance(self.mesh, MeshSpec):
            object.__setattr__(self, "mesh", MeshSpec.parse(self.mesh))

    def cache_spec(self) -> CacheSpec:
        return CacheSpec(self.page_size, self.n_pages, self.max_len)
