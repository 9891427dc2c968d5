"""Continuous-batching serving engine over a paged KV cache.

The engine is split into a host-side :class:`Scheduler` (admission control,
the slot state machine, chunk budgeting) and a device-side
:class:`ModelRunner` (the compiled functions and the cache pytree), with
:class:`Engine` as the public facade driving one *unified mixed step* per
tick: up to ``chunk_tokens`` of prompt-chunk work from the prefilling slot
plus one decode token per decoding slot, packed into a single compiled call.
Decode latency stays flat while long prompts stream through in fixed-size
chunks — prefill no longer head-of-line-blocks in-flight decodes.

Slot state machine (``Scheduler``)::

    QUEUED --admit--> PREFILLING(offset) --chunks--> DECODING --eos/limit-->
    RETIRED

Every retirement carries a :class:`FinishReason`: ``STOP``/``LENGTH`` are
the healthy exits; ``DEADLINE`` (per-request budget expired), ``CANCELLED``
(:meth:`Engine.cancel` / :meth:`Engine.close`), ``PREEMPTED`` (evicted under
page pressure with ``preemption="drop"``), ``FAULT`` (non-finite logits —
the slot is isolated, the rest of the batch continues) and ``REJECTED``
(bounded-queue admission refused — never a silent drop) are the degraded
ones.

Admission reserves the request's page need up front — the *full* need
(prompt + max_new rows) by default, or just the prompt rows when
``EngineConfig.preemption`` is enabled (lazy growth: decode rows are
allocated tick by tick, and on pool exhaustion the Scheduler evicts from
the radix tree, then *preempts* the lowest-priority decoding slot — fewest
tokens generated, ties by latest arrival — frees its pages and requeues it;
on re-admission its generated tokens are recomputed via normal chunked
prefill, with radix prefix hits making the recompute cheap, and greedy
outputs stay bit-identical to the never-preempted run).  On
prefix-decomposable models (pure attention) a slot starts at ``offset =
radix prefix hit``; each tick the mixed step advances the oldest prefilling
slot by up to ``chunk_tokens`` prompt rows, writing chunk KV straight
through the page table (``model.chunk_step`` — no dense gather of the
past).  When the chunk completes the prompt, the chunk logits' last valid
row samples the first token and the slot flips to DECODING.  Ticks with no
prefill work run a ``lax.scan`` of ``decode_chunk`` fused decode steps as
before.

Compiled-variant budget: the mixed step compiles once per chunk *buffer*
size — with ``chunk_tokens`` set that is one variant total; unset, the
whole suffix runs as a single chunk in a power-of-two-bucketed buffer
(≤ log2(max_len) variants).  This replaces the per-``(prefix_len,
suffix_len)`` prefill executable cache; the LRU bound
(``Engine.max_prefill_variants``) is kept as a backstop and still governs
the exact-length whole-prompt path used by non-decomposable mixers
(SSM / MLA / cross-attention), which cannot chunk.

Cache layout (``EngineConfig.cache_spec()``): every
attention layer owns a head-major ``[n_pages, K, page_size, d]`` page pool
allocated up front via ``model.paged_cache_specs``; each live sequence holds a page
*table* (``[pages_per_seq]`` int32, shared logically across all layers —
pages are allocated in lockstep) mapping logical KV rows to pool pages.
Page 0 is the reserved *trash page*: retired batch rows keep their table
zeroed and ``pos = 0``, so the decode chunk's unconditional writes land
somewhere harmless; the mixed step likewise zeroes the prefilling slot's
row in the decode-side table.

Prefix reuse (``EngineConfig.prefix_cache``): a radix tree over page-sized
token chunks (``serving.paging.RadixCache``) shares full prompt pages
between requests by refcount — a prefix hit of ``s`` tokens skips their
recompute entirely: the slot starts prefilling at ``offset = s`` and the
chunks cover only the suffix.  A partially-matching page is shared
copy-on-write: the new request gets a fresh page, the donor's matched rows
are device-copied, and the chunks overwrite the divergent tail.  A prompt's
full pages are published to the tree when its prefill *completes* (pages
must be fully written before they can be matched), and admission holds
while a slot is prefilling so lookups never race an unpublished prefix.

Fault isolation: every compiled step carries a per-slot non-finite check on
the sampled logits — a poisoned slot (NaN/Inf from bad weights, a flaky
device, or the chaos harness's ``logits.nan`` point) freezes in-graph on
the faulty step and retires with ``FinishReason.FAULT``; slots are
KV-independent, so the rest of the batch is unaffected (MoE joint routing
is the documented exception).  The deterministic chaos harness
(:mod:`repro.serving.chaos`) drives all of these paths from seeded fault
schedules; ``Engine(..., chaos=ChaosInjector(...))`` also reroutes the
engine's clock through the injector so deadline storms are reproducible.

Per-slot determinism: each request carries its own PRNG key and temperature,
and every slot decodes at its own position, so a request's output is
independent of whatever shares the batch with it.  (Exception: MoE layers —
expert capacity is routed jointly over the batch, so under capacity pressure
a request's routing can depend on concurrent traffic, as on any batched MoE
serving system.)
"""
from __future__ import annotations

import functools
import time
import warnings
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.core import round_up
from repro.launch.sharding import activation_mesh, psum_bytes, tree_pspecs
from repro.models import model as M
from repro.models.params import is_spec
from repro.serving.chaos import ChaosError, ChaosInjector
from repro.serving.config import CacheSpec, EngineConfig
from repro.serving.paging import (PagePool, PrefixMatch, RadixCache,
                                  check_invariants)


def bytes_tokenizer_encode(text: str, vocab: int) -> list[int]:
    return [b % vocab for b in text.encode("utf-8")]


def bytes_tokenizer_decode(tokens) -> str:
    return bytes(int(t) % 256 for t in tokens).decode("utf-8", errors="replace")


# ---------------------------------------------------------------------------
# Requests / results
# ---------------------------------------------------------------------------

class FinishReason(str, Enum):
    """Why a request retired.  ``STOP``/``LENGTH`` are healthy completions;
    everything else is a degraded exit (see the state machine in the module
    docstring and DESIGN.md §10)."""
    STOP = "stop"            # emitted eos_id
    LENGTH = "length"        # emitted max_new tokens
    DEADLINE = "deadline"    # per-request deadline expired
    CANCELLED = "cancelled"  # Engine.cancel / Engine.close
    PREEMPTED = "preempted"  # evicted under page pressure (preemption="drop")
    FAULT = "fault"          # non-finite logits: slot isolated from the batch
    REJECTED = "rejected"    # bounded queue refused admission at submit


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 32
    temperature: float = 0.0
    seed: int = 0
    arrival_s: float = 0.0
    #: optional wall-clock budget (seconds, relative to arrival); past it the
    #: request retires DEADLINE wherever it is (queued or in flight)
    deadline_s: float | None = None
    # -- preemption/recompute carry-state (engine-internal) ----------------
    #: tokens generated before a preemption; on re-admission the slot
    #: prefills prompt + resume_tokens and continues where it left off
    resume_tokens: list[int] = field(default_factory=list)
    resume_key: Any = None       # PRNG key as of the preemption point
    first_token_s: float | None = None
    token_times: list[float] = field(default_factory=list)
    preemptions: int = 0
    #: engine clock at the first admission (a preempted request keeps it)
    admit_s: float | None = None
    #: prompt rows the radix cache supplied at that admission
    prefix_hit: int | None = None

    def full_prompt(self) -> list[int]:
        """Rows to prefill: the prompt plus any tokens generated before a
        preemption (recompute path — already-sampled tokens are ordinary
        prefill input the second time around)."""
        return list(self.prompt) + list(self.resume_tokens)


@dataclass
class RequestResult:
    rid: int
    prompt: list[int]
    generated: list[int]
    arrival_s: float
    first_token_s: float
    finish_s: float
    #: wall-clock emission time of each generated token (tick granularity —
    #: tokens emitted by the same compiled call share a timestamp); drives
    #: inter-token-latency percentiles in the serving benchmark
    token_times_s: list[float] = field(default_factory=list)
    finish_reason: FinishReason = FinishReason.LENGTH
    #: backpressure hint on REJECTED results: seconds after which a retry
    #: plausibly finds queue room (estimated from in-flight progress)
    retry_after_s: float | None = None
    #: engine clock at the first admission; ``None`` for a request that
    #: never left the queue
    admit_s: float | None = None
    #: prompt rows the radix cache supplied at that admission
    prefix_hit: int | None = None

    @property
    def ok(self) -> bool:
        """True for healthy completions (STOP / LENGTH)."""
        return self.finish_reason in (FinishReason.STOP, FinishReason.LENGTH)

    @property
    def tokens(self) -> list[int]:
        return list(self.prompt) + list(self.generated)

    @property
    def ttft_s(self) -> float:
        return self.first_token_s - self.arrival_s

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s

    @property
    def itl_s(self) -> list[float]:
        """Inter-token gaps (seconds) between consecutive emissions."""
        t = self.token_times_s
        return [b - a for a, b in zip(t, t[1:])]


@dataclass
class ServeStats:
    #: host seconds from dispatch to sync of the mixed steps / decode chunks
    prefill_s: float = 0.0
    decode_s: float = 0.0
    #: host seconds ``Engine.step`` spends outside its tick's dispatch and
    #: sync: the ``engine.schedule`` and ``engine.emit`` spans, the latter
    #: with the mixed step's read of its chunk's flag and first token (a
    #: non-decomposable mixer's whole-prompt prefill runs inside admission,
    #: so it counts here as well as in ``prefill_s``)
    host_s: float = 0.0
    #: bytes per device that the all-reduces of the dispatched mixed steps
    #: and decode chunks carried, each call counted from the shapes its
    #: executable was traced with (``launch.sharding.psum_bytes``); 0
    #: without a mesh
    collective_bytes: int = 0
    tokens_out: int = 0
    prefills: int = 0
    chunks: int = 0
    mixed_steps: int = 0
    peak_active: int = 0
    prefix_hit_tokens: int = 0
    prefix_lookup_tokens: int = 0
    # resilience counters: one increment per event (see tests/test_resilience)
    preempted: int = 0
    rejected: int = 0
    deadline_expired: int = 0
    cancelled: int = 0
    faults_isolated: int = 0

    @property
    def tokens_per_s(self) -> float:
        """Tokens emitted per second of compiled calls: mixed steps emit
        tokens too, so both ``prefill_s`` and ``decode_s`` count (host time
        outside the calls does not)."""
        busy = self.prefill_s + self.decode_s
        return self.tokens_out / busy if busy else 0.0


QUEUED = "queued"
PREFILLING = "prefilling"
DECODING = "decoding"


@dataclass
class _Slot:
    req: Request
    emitted: list[int] = field(default_factory=list)
    first_token_s: float = 0.0
    phase: str = DECODING
    offset: int = 0        # prompt rows already in pages (incl. radix hit)
    seq: int = 0           # admission order (FIFO chunk scheduling)
    key: Any = None        # request PRNG key until the first sample commits
    token_times: list[float] = field(default_factory=list)


@dataclass
class _Tick:
    """One compiled call as the schedule phase leaves it: the executable,
    its host inputs after ``(params, caches)``, ``remaining`` at tick start,
    the ``engine.dispatch`` span's arguments, and the mixed step's prompt
    chunk ``(slot, rows)`` (``None`` for a decode chunk)."""
    fn: Any
    inputs: tuple
    before: np.ndarray
    span: dict
    chunk: tuple[int, int] | None = None


_LEGACY_KWARGS = ("max_len", "max_slots", "prefill_bucket", "decode_chunk",
                  "eos_id", "max_queue", "kernel_mode", "quant")


# ---------------------------------------------------------------------------
# ModelRunner: the compiled pieces + the cache pytree
# ---------------------------------------------------------------------------

class ModelRunner:
    """Owns the device state (params, paged cache pools) and every compiled
    function the engine calls: the fused decode chunk, the unified mixed
    step (one compiled variant per chunk-buffer size), the exact-length
    whole-prompt prefill for non-decomposable mixers, and the COW page copy.
    Executables live in one LRU (`fns`) bounded by the caller-supplied
    variant limit."""

    def __init__(self, cfg: ArchConfig, params, config: EngineConfig):
        self.cfg = cfg
        self.page_size = config.page_size
        self.decode_chunk = config.decode_chunk
        self.eos_id = config.eos_id
        self.vocab = cfg.vocab_size
        # mesh-sharded serving: place params with the logical-axis TP rules
        # and every KV pool over its kv_heads axis (page tables stay
        # replicated host-side numpy — the Scheduler is device-agnostic)
        self.mesh = (config.mesh.build()
                     if config.mesh is not None and config.mesh.size > 1
                     else None)
        #: the mesh's model (tensor-parallel) axis; 1 without a mesh
        self.tp = 1
        if self.mesh is not None:
            params = M.shard_params(cfg, params, self.mesh)
            self.tp = dict(self.mesh.shape)["model"]
        self.params = params
        self.cache_specs = M.paged_cache_specs(cfg, config.max_batch,
                                               config.n_pages,
                                               config.page_size)

        def zeros(spec, sharding=None):
            return jnp.zeros(spec.shape, spec.dtype or cfg.compute_dtype,
                             device=sharding)

        # under a mesh each device allocates only its shard: a whole pool
        # made on one device first would hold the model's full KV there
        trees = [self.cache_specs]
        if self.mesh is not None:
            trees.append(tree_pspecs(self.cache_specs, self.mesh))
        self.caches = jax.tree.map(zeros, *trees, is_leaf=is_spec)
        self.decode_fn = jax.jit(self._traced(self._decode_chunk),
                                 donate_argnums=(1,))
        self.copy_fn = jax.jit(self._traced(self._copy_page),
                               donate_argnums=(0,))
        self.fns: OrderedDict[tuple, Any] = OrderedDict()
        # weak keys: an executable the LRU above evicts is not kept alive
        self._psum_bytes: weakref.WeakKeyDictionary = (
            weakref.WeakKeyDictionary())

    def psum_bytes(self, fn, args) -> int:
        """Bytes per device that one call of the jitted ``fn`` on ``args``
        all-reduces, counted once per executable from the jaxpr its first
        call traces (``jit``'s trace cache then serves the call itself)."""
        if self.mesh is None:
            return 0
        n = self._psum_bytes.get(fn)
        if n is None:
            n = self._psum_bytes[fn] = psum_bytes(fn.trace(*args).jaxpr)
        return n

    def _traced(self, fn):
        """Trace-time mesh context: the model's ``constrain`` calls (and the
        Pallas ``shard_map`` wrappers) only see the mesh if it is set while
        jit *traces* the function, not when the executable is called."""
        if self.mesh is None:
            return fn

        @functools.wraps(fn)
        def wrapped(*args):
            with activation_mesh(self.mesh):
                return fn(*args)

        return wrapped

    # -- sampling / decode ------------------------------------------------

    def _sample_lf(self, lf, temp, keys):
        """Per-slot sampling from f32 vocab logits.  lf: [B,V]; temp: [B];
        keys: [B,2] u32."""
        greedy = jnp.argmax(lf, -1).astype(jnp.int32)

        def one(key, lg, t):
            return jax.random.categorical(key, lg / jnp.maximum(t, 1e-6))

        sampled = jax.vmap(one)(keys, lf, temp).astype(jnp.int32)
        nxt = jnp.where(temp > 0.0, sampled, greedy)
        keys = jax.vmap(lambda k: jax.random.split(k, 2)[1])(keys)
        return nxt, keys

    def _sample(self, logits, temp, keys):
        """Per-slot sampling.  logits: [B,Vp]; temp: [B]; keys: [B,2] u32."""
        return self._sample_lf(logits[:, : self.vocab].astype(jnp.float32),
                               temp, keys)

    def _dec_body(self, params, pages, temp, nanmask):
        """One decode step as a scan body — shared verbatim between the
        decode-only chunk and the mixed step, so a token's math does not
        depend on which tick shape produced it.

        Fault isolation happens here, in-graph: a live slot whose logits go
        non-finite (``nanmask`` injects NaN for the chaos harness) freezes
        immediately — no token is taken, ``remaining`` drops to 0 — and the
        per-step ``ok`` flag tells the host which step went bad.  Slots that
        are already frozen decode trash-page garbage by design, so only
        *active* slots can fault."""
        cfg = self.cfg

        def body(carry, _):
            caches, cur, pos, remaining, keys = carry
            active = remaining > 0
            logits, caches = M.decode_step(cfg, params, caches, cur[:, None],
                                           pos, pages=pages)
            lf = logits[:, -1, : self.vocab].astype(jnp.float32)
            lf = jnp.where(nanmask[:, None], jnp.nan, lf)
            finite = jnp.all(jnp.isfinite(lf), -1)
            nxt, keys = self._sample_lf(lf, temp, keys)
            ok = finite | ~active      # a frozen slot cannot fault
            nxt = jnp.where(active & finite, nxt, cur)
            step = (active & finite).astype(jnp.int32)
            remaining = jnp.where(ok, remaining - step, 0)
            if self.eos_id is not None:
                remaining = jnp.where(active & finite & (nxt == self.eos_id),
                                      0, remaining)
            return (caches, nxt, pos + step, remaining, keys), (nxt, ok)

        return body

    def _decode_chunk(self, params, caches, pages, cur, pos, remaining, temp,
                      keys, nanmask):
        """``decode_chunk`` fused decode steps; emits [B, steps] tokens plus
        the matching [B, steps] per-step fault flags.  ``pages`` [B, npp] is
        constant across the chunk (each request's page need for the chunk is
        reserved before the tick); finished slots freeze — their table is
        re-pointed at the trash page on retirement, so the chunk's
        unconditional KV writes can never corrupt a reallocated page."""
        (caches, cur, pos, remaining, keys), (toks, oks) = lax.scan(
            self._dec_body(params, pages, temp, nanmask),
            (caches, cur, pos, remaining, keys), None,
            length=self.decode_chunk)
        return caches, cur, pos, remaining, keys, toks.T, oks.T  # [B, steps]

    # -- the unified mixed step -------------------------------------------

    def _mixed(self, params, caches, chunk_toks, chunk_pages, chunk_past,
               chunk_len, chunk_temp, chunk_key, chunk_nan, dec_pages, cur,
               pos, remaining, temp, keys, nanmask):
        """One engine tick: a prompt chunk for the prefilling slot plus one
        decode step for every decoding slot, in a single compiled call.

        chunk_toks [1, C] (``chunk_len`` valid rows at absolute positions
        ``chunk_past + i``), chunk_pages [1, npp].  ``dec_pages`` is the
        batch page table with the prefilling slot's row zeroed, so the
        decode pass's unconditional write for that (frozen) row lands on the
        trash page.  The chunk's sampled token/key only matter on the tick
        the chunk completes the prompt — the host discards them otherwise.
        ``chunk_ok`` is the chunk-side fault flag (the chunk logits are the
        last *valid* row, so non-finite means the prefilling slot is
        poisoned regardless of which tick it is)."""
        logits, caches = M.chunk_step(self.cfg, params, caches, chunk_toks,
                                      chunk_pages, chunk_past, chunk_len)
        lf = logits[:, -1, : self.vocab].astype(jnp.float32)
        lf = jnp.where(chunk_nan, jnp.nan, lf)
        chunk_ok = jnp.all(jnp.isfinite(lf))
        tok0, key0 = self._sample_lf(lf, chunk_temp[None], chunk_key[None])
        (caches, cur, pos, remaining, keys), (toks, oks) = lax.scan(
            self._dec_body(params, dec_pages, temp, nanmask),
            (caches, cur, pos, remaining, keys), None, length=1)
        return (caches, tok0[0], key0[0], chunk_ok, cur, pos, remaining,
                keys, toks.T, oks.T)

    def mixed_fn(self, C: int, limit: int):
        """The mixed-step executable for chunk-buffer size ``C`` (the only
        shape degree of freedom — chunk offset/length are traced scalars)."""
        return self._cached(
            ("mixed", C),
            lambda: jax.jit(self._traced(self._mixed), donate_argnums=(1,)),
            limit)

    # -- exact-length whole-prompt prefill (non-decomposable mixers) ------

    def _whole_prefill(self, params, caches, tokens, table, slot, temp1,
                       rkey):
        """Exact-length whole-prompt prefill + cache insert (traceable —
        ``repro.analysis`` walks this jaxpr; ``whole_prefill_fn`` jits it).
        ``ok`` is the fault flag over the sampled logits row."""
        logits, small = M.prefill(self.cfg, params, {"tokens": tokens})
        caches = M.write_prefill(self.cfg, caches, small, table, slot)
        lf = logits[:, -1, : self.vocab].astype(jnp.float32)
        ok = jnp.all(jnp.isfinite(lf))
        t0, key1 = self._sample_lf(lf, temp1[None], rkey[None])
        return caches, t0[0], key1[0], ok

    def whole_prefill_fn(self, n: int, limit: int):
        """Jitted exact-length prefill + cache insert for mixers whose
        prefill is not prefix-decomposable (SSM / MLA / cross-attention —
        they cannot run as chunks over a paged past).  One compilation per
        prompt length, LRU-bounded like the mixed variants."""
        return self._cached(
            ("whole", n),
            lambda: jax.jit(self._traced(self._whole_prefill),
                            donate_argnums=(1,)),
            limit)

    def _cached(self, key, build, limit: int):
        fn = self.fns.pop(key, None)
        if fn is None:
            fn = build()
        self.fns[key] = fn  # (re)insert as most recently used
        while len(self.fns) > limit:
            self.fns.popitem(last=False)
        return fn

    # -- COW page copy ----------------------------------------------------

    def _copy_page(self, caches, src, dst):
        """Device copy page ``src`` -> ``dst`` in every KV pool (the COW half
        of a partial-page prefix share; the chunk prefill then overwrites
        the divergent tail rows of ``dst``)."""

        def cp(spec, pool):
            if "kv_seq" not in spec.axes:
                return pool
            return pool.at[:, dst].set(pool[:, src])

        return jax.tree.map(cp, self.cache_specs, caches, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Scheduler: admission, chunk budgeting, slot state machine
# ---------------------------------------------------------------------------

class Scheduler:
    """Host-side request bookkeeping: the bounded admission queue, per-slot
    numpy state (page tables, positions, budgets, PRNG keys), page/radix
    accounting, and the QUEUED → PREFILLING → DECODING → RETIRED state
    machine (with the degraded exits — DEADLINE / CANCELLED / PREEMPTED /
    FAULT — layered on).  It decides *what* runs each tick (`next_chunk`);
    the :class:`ModelRunner` decides *how*."""

    def __init__(self, config: EngineConfig, decomposable: bool,
                 clock=time.time):
        B = config.max_batch
        self.config = config
        self.clock = clock
        self.page_size = config.page_size
        self.max_batch = B
        self.npp = config.cache_spec().pages_per_seq
        self.pool = PagePool(config.n_pages)
        # preemption implies lazy page reservation: admission takes only the
        # prompt's pages and decode rows grow tick by tick, so the pool can
        # oversubscribe and preemption resolves the pressure
        self.lazy = config.preemption != "off"
        # Chunked prefill (and prefix reuse) require prefill to decompose
        # over the prompt: pure attention (incl. sliding-window) qualifies;
        # SSM mixers scan state across the whole prompt, cross-attn prefill
        # depends on the image, and this MLA prefill recomputes absorbed
        # latents — all excluded, and served by exact whole-prompt prefill.
        self.chunked = decomposable
        self.radix: RadixCache | None = (
            RadixCache(config.page_size, self.pool)
            if (config.prefix_cache and decomposable) else None)

        self.pages = np.zeros((B, self.npp), np.int32)  # 0 == trash page
        self.owned: list[list[int]] = [[] for _ in range(B)]  # page refs
        self.cur = np.zeros(B, np.int32)        # next input token per slot
        self.pos = np.zeros(B, np.int32)        # its logical cache row
        self.limit = np.zeros(B, np.int32)      # reserved rows (plen+max_new)
        self.remaining = np.zeros(B, np.int32)  # tokens still to emit
        self.temp = np.zeros(B, np.float32)
        self.keys = np.zeros((B, 2), np.uint32)

        self.queue: deque[Request] = deque()
        self.slots: list[_Slot | None] = [None] * B
        self.finished: list[RequestResult] = []
        self._seq = 0

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def num_queued(self) -> int:
        return len(self.queue)

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new) // self.page_size)

    def prefilling_slot(self) -> int | None:
        """Index of the slot currently streaming its prompt (at most one:
        admission holds while a prefill is in flight)."""
        cands = [i for i, s in enumerate(self.slots)
                 if s is not None and s.phase == PREFILLING]
        if not cands:
            return None
        return min(cands, key=lambda j: self.slots[j].seq)

    def next_chunk(self) -> tuple[int, int] | None:
        """(slot, n): the next prompt chunk to run — up to ``chunk_tokens``
        rows of the oldest prefilling slot (the whole remaining suffix when
        chunking is off)."""
        i = self.prefilling_slot()
        if i is None:
            return None
        slot = self.slots[i]
        left = len(slot.req.full_prompt()) - slot.offset
        ct = self.config.chunk_tokens
        return i, (left if ct is None else min(ct, left))

    def _ensure_free_pages(self, fresh_needed: int) -> bool:
        """True when the pool can supply ``fresh_needed`` pages, evicting
        radix-cached pages only if eviction actually gets there — a request
        that stays blocked must not cost the tree pages it cannot use."""
        if self.pool.num_free >= fresh_needed:
            return True
        if self.radix is None:
            return False
        if self.pool.num_free + self.radix.num_evictable() < fresh_needed:
            return False
        self.radix.evict(fresh_needed)
        return True

    # -- degraded exits ---------------------------------------------------

    def queue_result(self, req: Request, now: float,
                     reason: FinishReason) -> RequestResult:
        """Result for a request that exits without (re)gaining a slot —
        rejected / expired / cancelled while queued.  Tokens generated
        before a preemption are preserved (never silently dropped)."""
        return RequestResult(
            req.rid, req.prompt, list(req.resume_tokens), req.arrival_s,
            req.first_token_s if req.first_token_s is not None else now,
            now, token_times_s=list(req.token_times), finish_reason=reason,
            admit_s=req.admit_s, prefix_hit=req.prefix_hit)

    def expire(self, now: float, stats: ServeStats):
        """Retire every request whose deadline has passed — queued requests
        exit empty-handed; in-flight slots keep their partial output."""
        for req in [r for r in self.queue
                    if r.deadline_s is not None
                    and now - r.arrival_s > r.deadline_s]:
            self.queue.remove(req)
            stats.deadline_expired += 1
            self.finished.append(
                self.queue_result(req, now, FinishReason.DEADLINE))
        for i, slot in enumerate(self.slots):
            if (slot is not None and slot.req.deadline_s is not None
                    and now - slot.req.arrival_s > slot.req.deadline_s):
                stats.deadline_expired += 1
                self.retire(i, now, FinishReason.DEADLINE)

    def cancel(self, rid: int, now: float, stats: ServeStats) -> bool:
        """Cancel a request wherever it is; False if unknown/finished."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                stats.cancelled += 1
                self.finished.append(
                    self.queue_result(req, now, FinishReason.CANCELLED))
                return True
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.req.rid == rid:
                stats.cancelled += 1
                self.retire(i, now, FinishReason.CANCELLED)
                return True
        return False

    def _pick_victim(self) -> int | None:
        """Preemption victim policy: the lowest-priority DECODING slot —
        fewest tokens generated, ties broken by latest arrival (newest
        request yields first).  The slot asking for pages is a candidate
        like any other: when it is itself the lowest-priority slot it
        yields (self-preempts) rather than stealing from above."""
        cands = [i for i, s in enumerate(self.slots)
                 if s is not None and s.phase == DECODING]
        if not cands:
            return None
        return min(cands, key=lambda j: (len(self.slots[j].emitted),
                                         -self.slots[j].req.arrival_s,
                                         -self.slots[j].seq))

    def preempt(self, i: int, stats: ServeStats):
        """Evict slot ``i``: free its pages and either requeue it for
        recompute (``preemption="recompute"`` — generated tokens re-enter as
        prefill input, so greedy output stays bit-identical) or retire it
        with its partial output (``preemption="drop"`` — load shedding)."""
        now = self.clock()
        slot = self.slots[i]
        req = slot.req
        stats.preempted += 1
        if self.config.preemption == "drop" \
                or len(slot.emitted) >= req.max_new:
            self.retire(i, now, FinishReason.PREEMPTED)
            return
        req.resume_tokens = list(slot.emitted)
        req.resume_key = np.array(self.keys[i])
        req.first_token_s = slot.first_token_s
        req.token_times = list(slot.token_times)
        req.preemptions += 1
        self.slots[i] = None
        for pid in self.owned[i]:
            self.pool.decref(pid)
        self.owned[i] = []
        self.pages[i] = 0
        self.pos[i] = self.cur[i] = self.remaining[i] = 0
        self.queue.appendleft(req)  # preempted requests keep queue priority

    def ensure_rows(self, i: int, rows: int, stats: ServeStats) -> bool:
        """Lazy page growth: make slot ``i``'s table cover ``rows`` logical
        rows, allocating pages on demand.  On exhaustion: radix-evict, then
        preempt the lowest-priority decoding slot — ``i`` itself when it is
        the lowest (requeue — never raise).  Returns False when ``i`` no
        longer holds its slot."""
        need = -(-rows // self.page_size)
        tries = 0
        while len(self.owned[i]) < need:
            pid = self.pool.alloc()
            if pid is not None:
                self.pages[i][len(self.owned[i])] = pid
                self.owned[i].append(pid)
                tries = 0
                continue
            tries += 1
            if tries <= 2 and self._ensure_free_pages(1):
                continue  # radix evicted / transient alloc fault: retry
            victim = self._pick_victim()
            if victim is None or victim == i:
                self.preempt(i, stats)  # i is lowest-priority: yield
                return False
            self.preempt(victim, stats)
            tries = 0
        return True

    def grow_for_decode(self, steps_bound: int, stats: ServeStats):
        """Before a tick, grow every decoding slot's page table to cover the
        rows the next ``steps_bound`` decode steps will write.  Growth runs
        in descending priority order, so under pressure the high-priority
        slots claim pages first and the victim policy preempts from the
        bottom."""
        order = sorted(
            [i for i, s in enumerate(self.slots)
             if s is not None and s.phase == DECODING],
            key=lambda j: (-len(self.slots[j].emitted),
                           self.slots[j].req.arrival_s, self.slots[j].seq))
        for i in order:
            if self.slots[i] is None:
                continue  # preempted as a victim earlier in this pass
            steps = min(int(self.remaining[i]), steps_bound)
            if steps:
                self.ensure_rows(i, int(self.pos[i]) + steps, stats)

    # -- admission --------------------------------------------------------

    def admit(self, runner: ModelRunner, stats: ServeStats,
              variant_limit: int):
        """Move queued requests into free batch rows.  FIFO with
        head-of-line blocking: when the head request's page need cannot be
        met even after radix eviction, admission stops until retirements
        free pages (no starvation of large requests).  With preemption
        enabled, admission reserves only the prompt's pages (decode rows
        grow lazily).  On chunked (prefix-decomposable) models a newly
        admitted slot enters PREFILLING and admission holds until its
        prefill completes — lookups must never match pages that are not
        fully written and published; non-decomposable models prefill whole
        prompts inline.  A preempted request re-enters here: its prompt plus
        already-generated tokens prefill as one sequence (radix hits make
        that cheap), and its saved PRNG key resumes the sample chain."""
        free_rows = [i for i in range(self.max_batch)
                     if self.slots[i] is None]
        while self.queue and free_rows:
            if self.chunked and self.prefilling_slot() is not None:
                break
            req = self.queue[0]
            full = req.full_prompt()
            plen = len(full)
            new_budget = req.max_new - len(req.resume_tokens)
            need = (self.pages_needed(plen, 0) if self.lazy
                    else self.pages_needed(plen, new_budget))
            if self.radix is not None:
                ht, lt = self.radix.hit_tokens, self.radix.lookup_tokens
                m = self.radix.match(full, max_match=plen - 1)
            else:
                m = PrefixMatch()
            fresh_needed = need - len(m.full_pages)
            # Pin every matched page (and the COW donor) *before* eviction
            # can run: tree-only pages (refcount 1) are legitimate LRU
            # victims, and an unpinned match could be freed by the very
            # evict() that makes room for its own suffix — the page table
            # would then point at a page the pool hands to someone else.
            pinned = list(m.full_pages)
            if m.partial is not None:
                pinned.append(m.partial[0])
            for pid in pinned:
                self.pool.incref(pid)
            ok = self._ensure_free_pages(fresh_needed)
            if not ok and m.partial is not None:
                # The pinned donor may itself be the one page eviction is
                # short of (a request sized to the whole pool); retry with
                # the copy-on-write share dropped rather than deadlock.
                self.pool.decref(pinned.pop())
                self.radix.hit_tokens -= m.partial[1]
                m.partial = None
                m.tokens = len(m.full_pages) * self.page_size
                ok = self._ensure_free_pages(fresh_needed)
            fresh: list[int] = []
            if ok:
                for _ in range(fresh_needed):
                    pid = self.pool.alloc()
                    if pid is None:  # transient alloc fault (chaos)
                        break
                    fresh.append(pid)
                ok = len(fresh) == fresh_needed
            if not ok:
                for pid in fresh:
                    self.pool.decref(pid)
                for pid in pinned:
                    self.pool.decref(pid)
                if self.radix is not None:  # blocked: don't count the lookup
                    self.radix.hit_tokens = ht
                    self.radix.lookup_tokens = lt
                break
            self.queue.popleft()
            i = free_rows.pop(0)
            s = m.tokens  # cached prefix length (<= plen - 1)
            if req.admit_s is None:  # a readmission keeps the first record
                req.admit_s, req.prefix_hit = self.clock(), s
            shared = list(m.full_pages)  # pins transfer to slot ownership
            table = np.zeros(self.npp, np.int32)
            table[: len(shared)] = shared
            table[len(shared): len(shared) + len(fresh)] = fresh
            if m.partial is not None:  # copy-on-write share of a partial page
                donor, _rows = m.partial
                runner.caches = runner.copy_fn(runner.caches,
                                               jnp.int32(donor),
                                               jnp.int32(fresh[0]))
                self.pool.decref(donor)  # COW copy done: release the pin

            key = (np.asarray(req.resume_key) if req.resume_key is not None
                   else np.asarray(
                       jax.random.PRNGKey(req.seed ^ (req.rid * 0x9E3779B9))))
            self.pages[i] = table
            self.owned[i] = shared + fresh
            self.limit[i] = plen + new_budget
            self.temp[i] = req.temperature
            if self.chunked:
                # slot enters PREFILLING at the radix offset; the engine's
                # mixed ticks stream the suffix through in chunks
                slot = _Slot(req, emitted=list(req.resume_tokens),
                             first_token_s=req.first_token_s or 0.0,
                             phase=PREFILLING, offset=s, seq=self._seq,
                             key=key, token_times=list(req.token_times))
                self._seq += 1
                self.slots[i] = slot
                self.cur[i] = self.pos[i] = self.remaining[i] = 0
                break  # hold admission until this prefill completes
            # non-decomposable: exact-length whole-prompt prefill, inline
            assert s == 0 and m.partial is None
            toks = np.asarray(full, np.int32)[None]
            t0 = time.time()
            runner.caches, first, key1, pok = runner.whole_prefill_fn(
                plen, variant_limit)(
                    runner.params, runner.caches, jnp.asarray(toks),
                    jnp.asarray(table), jnp.int32(i),
                    jnp.float32(req.temperature), jnp.asarray(key))
            first = int(first)
            stats.prefill_s += time.time() - t0
            stats.prefills += 1
            now = self.clock()
            slot = _Slot(req, emitted=list(req.resume_tokens),
                         first_token_s=req.first_token_s or now,
                         phase=DECODING, seq=self._seq,
                         token_times=list(req.token_times))
            self._seq += 1
            self.slots[i] = slot
            if not bool(pok):  # poisoned prefill: isolate this request
                stats.faults_isolated += 1
                self.retire(i, now, FinishReason.FAULT)
                free_rows.append(i)
                continue
            slot.emitted.append(first)
            slot.token_times.append(now)
            self.cur[i], self.pos[i] = first, plen
            self.remaining[i] = req.max_new - len(slot.emitted)
            self.keys[i] = np.asarray(key1)
            stats.tokens_out += 1
            if self.remaining[i] == 0 or first == self.config.eos_id:
                self.remaining[i] = 0
                self.retire(i, now)
                free_rows.append(i)

    def commit_prefill(self, i: int, first: int, key1, now: float,
                       stats: ServeStats) -> bool:
        """A chunk just completed slot ``i``'s prompt: sample committed,
        slot flips to DECODING (or retires immediately on eos / max_new=1).
        Publishes the prompt's full pages to the radix tree — only now are
        they fully written and safe to match.  Returns True if retired."""
        slot = self.slots[i]
        req = slot.req
        full = req.full_prompt()
        plen = len(full)
        if self.radix is not None:
            fp = plen // self.page_size
            self.radix.insert(full[: fp * self.page_size],
                              [int(self.pages[i][j]) for j in range(fp)])
        slot.phase = DECODING
        slot.emitted = list(req.resume_tokens) + [first]
        slot.first_token_s = (req.first_token_s
                              if req.first_token_s is not None else now)
        slot.token_times = list(req.token_times) + [now]
        slot.key = None
        self.cur[i], self.pos[i] = first, plen
        self.remaining[i] = req.max_new - len(slot.emitted)
        self.keys[i] = np.asarray(key1)
        stats.prefills += 1
        stats.tokens_out += 1
        if self.remaining[i] == 0 or first == self.config.eos_id:
            self.remaining[i] = 0
            self.retire(i, now)
            return True
        return False

    def retire(self, i: int, now: float,
               reason: FinishReason | None = None):
        s = self.slots[i]
        if reason is None:
            reason = (FinishReason.STOP
                      if (self.config.eos_id is not None and s.emitted
                          and s.emitted[-1] == self.config.eos_id)
                      else FinishReason.LENGTH)
        self.finished.append(RequestResult(
            s.req.rid, s.req.prompt, s.emitted, s.req.arrival_s,
            s.first_token_s, now, token_times_s=list(s.token_times),
            finish_reason=reason, admit_s=s.req.admit_s,
            prefix_hit=s.req.prefix_hit))
        self.slots[i] = None
        for pid in self.owned[i]:
            self.pool.decref(pid)  # radix-held pages survive at rc >= 1
        self.owned[i] = []
        self.pages[i] = 0  # trash page: frozen-row writes land harmlessly
        self.pos[i] = 0
        self.cur[i] = 0
        self.remaining[i] = 0

    def check_capacity(self, steps_bound: int,
                       stats: ServeStats | None = None):
        """Refuse to decode a slot past its reserved rows.

        Rows beyond the reservation would route to the trash page (never
        corrupt another sequence), but reaching that state means silently
        lost context — the admission bound (``submit``) should have made it
        impossible.  With preemption enabled the engine degrades instead of
        raising: the slot is preempted (requeue or drop), which re-derives
        its accounting from scratch on re-admission.
        """
        for i, slot in enumerate(self.slots):
            if slot is None or slot.phase != DECODING:
                continue
            steps = min(int(self.remaining[i]), steps_bound)
            if self.pos[i] + steps <= self.limit[i]:
                continue
            if self.lazy and stats is not None:
                self.preempt(i, stats)
                continue
            raise RuntimeError(
                f"slot {i} (rid={slot.req.rid}): decoding {steps} "
                f"steps from pos={int(self.pos[i])} overruns KV capacity "
                f"{int(self.limit[i])} rows; request length accounting "
                f"is inconsistent with admission control")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class Engine:
    """Continuous-batching engine over a fixed params pytree.

    Construct with an :class:`~repro.serving.config.EngineConfig`::

        eng = Engine(cfg, params, EngineConfig(max_batch=8, max_len=512,
                                               page_size=64,
                                               chunk_tokens=32))

    The pre-paging keyword spelling (``max_slots=``, ``prefill_bucket=``,
    ...) still works through a ``DeprecationWarning`` shim: ``max_slots``
    maps to ``max_batch``, ``prefill_bucket`` is ignored (prefill is
    exact-length now), and the default page budget reproduces the legacy
    ``max_slots * max_len`` row capacity.

    Resilience surface: per-request deadlines (``submit(deadline_s=...)``),
    :meth:`cancel`, :meth:`close` (also the context-manager exit), bounded-
    queue rejection with a ``retry_after_s`` hint, and — behind
    ``EngineConfig(preemption=...)`` — page-pool preemption with recompute.
    Pass ``chaos=ChaosInjector(...)`` to drive the fault points
    deterministically (the injector also becomes the engine's clock).
    """

    #: Bound on cached executables in the runner's LRU: mixed-step variants
    #: (one per chunk-buffer size — a handful at most) plus exact-length
    #: whole-prompt prefills for non-decomposable mixers (one per prompt
    #: length — the reason the bound exists).
    max_prefill_variants: int = 32

    def __init__(self, cfg: ArchConfig, params,
                 config: EngineConfig | int | None = None,
                 chaos: ChaosInjector | None = None, **legacy):
        if isinstance(config, int):  # legacy positional: Engine(cfg, p, 512)
            legacy["max_len"] = config
            config = None
        if legacy:
            if config is not None:
                raise TypeError("pass either an EngineConfig or legacy "
                                "keyword arguments, not both")
            unknown = set(legacy) - set(_LEGACY_KWARGS)
            if unknown:
                raise TypeError(f"unknown Engine arguments: {sorted(unknown)}")
            warnings.warn(
                "Engine(max_len=..., max_slots=..., ...) is deprecated; pass "
                "EngineConfig (max_slots -> max_batch; prefill_bucket is "
                "gone — prefill is exact-length on the paged cache)",
                DeprecationWarning, stacklevel=2)
            legacy.pop("prefill_bucket", None)
            legacy["max_batch"] = legacy.pop("max_slots", 8)
            config = EngineConfig(**legacy)
        if config is None:
            config = EngineConfig()

        if config.kernel_mode is not None:
            cfg = cfg.with_(kernel_mode=config.kernel_mode)
        if config.quant is not None:
            cfg = cfg.with_(quant=config.quant)
        if cfg.quant == "w8a8":
            params = M.quantize_params(cfg, params)  # idempotent
        if config.mesh is not None and config.mesh.model > 1 \
                and cfg.num_experts and cfg.num_experts % config.mesh.model == 0:
            # expert-parallel decode: route tokens across the model axis via
            # the moe_specs/_moe_expert_block manual-axis path (each device
            # holds E/tp experts; the dispatch/combine gathers stay local
            # and one f32 psum merges the partial outputs)
            cfg = cfg.with_(moe_shard_map=True)
        self.cfg, self.params = cfg, params
        self.config = config
        self.cache_spec: CacheSpec = config.cache_spec()
        self.decode_chunk = config.decode_chunk
        self.chunk_tokens = config.chunk_tokens
        self.eos_id = config.eos_id
        self.max_queue = config.max_queue
        self.max_batch = config.max_batch
        self.max_len = config.max_len
        self.page_size = config.page_size
        self.npp = self.cache_spec.pages_per_seq
        self.stats = ServeStats()
        self.chaos = chaos
        self._closed = False

        decomposable = (not cfg.use_mla and
                        all(sp.mixer not in ("ssm", "cross")
                            for sp in cfg.layer_specs()))
        self.runner = ModelRunner(cfg, self.params, config)
        self.sched = Scheduler(config, decomposable, clock=self._now)
        if chaos is not None:
            self.sched.pool.fault = lambda: chaos.fire("pool.alloc")
        self._next_rid = 0

    def _now(self) -> float:
        """The engine clock — the chaos injector's skewed clock when one is
        attached (deterministic deadline storms), wall time otherwise."""
        return self.chaos.now() if self.chaos is not None else time.time()

    # -- state shared with the scheduler/runner (test-visible surface) ----

    @property
    def pool(self) -> PagePool:
        return self.sched.pool

    @property
    def radix(self) -> RadixCache | None:
        return self.sched.radix

    @property
    def num_active(self) -> int:
        return self.sched.num_active

    @property
    def num_queued(self) -> int:
        return self.sched.num_queued

    @property
    def prefix_hit_rate(self) -> float:
        return self.radix.hit_rate if self.radix else 0.0

    @property
    def _caches(self):
        return self.runner.caches

    @property
    def _prefill_fns(self):
        return self.runner.fns

    @property
    def _pages(self):
        return self.sched.pages

    @property
    def _remaining(self):
        return self.sched.remaining

    @property
    def _slots(self):
        return self.sched.slots

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        return self.sched.pages_needed(prompt_len, max_new)

    # -- admission --------------------------------------------------------

    def submit(self, prompt: list[int], max_new: int = 32,
               temperature: float = 0.0, seed: int = 0,
               deadline_s: float | None = None) -> int:
        """Admit a request; returns its rid.  Raises ``ValueError`` on
        malformed input or a request that can never fit (rows or pages —
        rejecting at submit time keeps an impossible request from
        head-of-line-blocking the queue forever).  Queue overflow does not
        raise: the request finishes immediately as ``REJECTED`` with a
        ``retry_after_s`` backpressure hint (collect it from ``step()`` /
        ``run()`` like any other result).  ``deadline_s`` (seconds from
        now; default ``EngineConfig.deadline_s``) bounds the request's
        wall-clock life across queueing and execution."""
        if self._closed:
            raise RuntimeError("engine is closed; create a new Engine")
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt: a request must carry at least "
                             "one prompt token")
        if not all(isinstance(t, (int, np.integer)) and 0 <= t < self.cfg.vocab_size
                   for t in prompt):
            raise ValueError(f"prompt tokens must be ints in "
                             f"[0, {self.cfg.vocab_size})")
        if not isinstance(max_new, (int, np.integer)) or max_new < 1:
            raise ValueError(f"max_new={max_new!r} must be an int >= 1")
        if temperature < 0.0:
            raise ValueError(f"temperature={temperature} must be >= 0")
        if deadline_s is None:
            deadline_s = self.config.deadline_s
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s={deadline_s} must be > 0")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"request needs {len(prompt) + max_new} cache rows > "
                f"max_len={self.max_len}")
        if self.pages_needed(len(prompt), max_new) > self.pool.n_pages - 1:
            raise ValueError(
                f"request needs {self.pages_needed(len(prompt), max_new)} "
                f"pages > pool capacity {self.pool.n_pages - 1}")
        now = self._now()
        rid = self._next_rid
        self._next_rid += 1
        if len(self.sched.queue) >= self.max_queue:
            self.stats.rejected += 1
            self.sched.finished.append(RequestResult(
                rid, [int(t) for t in prompt], [], now, now, now,
                finish_reason=FinishReason.REJECTED,
                retry_after_s=self._retry_hint()))
            return rid
        self.sched.queue.append(Request(rid, [int(t) for t in prompt],
                                        int(max_new), float(temperature),
                                        seed, arrival_s=now,
                                        deadline_s=deadline_s))
        return rid

    def _retry_hint(self) -> float:
        """Backpressure hint for REJECTED results: the least-remaining
        in-flight slot's tokens at the observed decode rate (fallback 50
        ms/token before any decode has run)."""
        rem = [int(self.sched.remaining[i])
               for i, s in enumerate(self.sched.slots) if s is not None]
        per_tok = (self.stats.decode_s / self.stats.tokens_out
                   if self.stats.tokens_out and self.stats.decode_s
                   else 0.05)
        return round(max(min(rem) if rem else 1, 1) * max(per_tok, 1e-3), 3)

    def cancel(self, rid: int) -> bool:
        """Cancel a request by rid — queued or in flight.  Partial output is
        returned as a ``CANCELLED`` result from the next ``step()``; pages
        free immediately.  False when the rid is unknown or already done."""
        return self.sched.cancel(rid, self._now(), self.stats)

    # -- shutdown ---------------------------------------------------------

    def close(self) -> list[RequestResult]:
        """Retire everything in flight as ``CANCELLED``, free all pages, and
        verify the paging state reconciles to its initial state (free list
        full, radix refcounts zeroed).  Returns the drained results.
        Idempotent; ``submit``/``step`` refuse after close."""
        if self._closed:
            return []
        sched = self.sched
        now = self._now()
        for req in list(sched.queue):
            sched.queue.remove(req)
            self.stats.cancelled += 1
            sched.finished.append(
                sched.queue_result(req, now, FinishReason.CANCELLED))
        for i, slot in enumerate(sched.slots):
            if slot is not None:
                self.stats.cancelled += 1
                sched.retire(i, now, FinishReason.CANCELLED)
        if sched.radix is not None:
            sched.radix.clear()
        bad = check_invariants(self.pool, sched.radix, tables=sched.owned)
        if self.pool.num_free != self.pool.n_pages - 1:
            bad.append(f"pool leaked pages: {self.pool.num_free} free != "
                       f"{self.pool.n_pages - 1} usable")
        assert not bad, ("close(): paging state failed to reconcile: "
                         + "; ".join(bad))
        self._closed = True
        out, sched.finished = sched.finished, []
        return out

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- the tick ---------------------------------------------------------

    def _chunk_buf(self, n: int) -> int:
        """Static chunk-buffer size for an ``n``-token chunk: exactly
        ``chunk_tokens`` when chunking is on (one compiled variant total);
        otherwise the next power-of-two bucket (≤ log2(max_len) variants
        across all prompt lengths — this replaces the per-(prefix, suffix)
        executable cache)."""
        if self.chunk_tokens is not None:
            return self.chunk_tokens
        C = 8
        while C < n:
            C *= 2
        return min(C, round_up(self.max_len, 8))

    def _nan_targets(self) -> tuple[np.ndarray, bool]:
        """Consult the ``logits.nan`` fault point: when it fires, poison the
        lowest-index live decoding slot (or, with none, the in-flight prompt
        chunk) for this tick."""
        nanmask = np.zeros(self.max_batch, bool)
        chunk_nan = False
        if self.chaos is not None and self.chaos.fire("logits.nan"):
            live = [j for j, s in enumerate(self.sched.slots)
                    if s is not None and s.phase == DECODING
                    and self.sched.remaining[j] > 0]
            if live:
                nanmask[live[0]] = True
            else:
                chunk_nan = True
        return nanmask, chunk_nan

    def _mixed_tick(self, i: int, n: int) -> _Tick:
        """Prepare the unified mixed step: ``n`` prompt rows of prefilling
        slot ``i`` plus one decode step for every decoding slot."""
        sched = self.sched
        if self.chaos is not None and self.chaos.fire("runner.mixed"):
            # pre-dispatch: no host or device state touched yet, so the
            # tick can simply be skipped and retried next step
            raise ChaosError("injected mixed-step failure")
        slot = sched.slots[i]
        full = slot.req.full_prompt()
        C = self._chunk_buf(n)
        buf = np.zeros((1, C), np.int32)
        buf[0, :n] = full[slot.offset: slot.offset + n]
        if sched.lazy:
            sched.grow_for_decode(1, self.stats)
        sched.check_capacity(1, self.stats)
        dec_pages = sched.pages.copy()
        dec_pages[i] = 0  # prefilling slot's frozen decode row -> trash page
        nanmask, chunk_nan = self._nan_targets()
        return self._tick(
            self.runner.mixed_fn(C, self.max_prefill_variants),
            (buf, sched.pages[i: i + 1], np.int32(slot.offset), np.int32(n),
             np.float32(slot.req.temperature), slot.key, np.bool_(chunk_nan),
             dec_pages, sched.cur, sched.pos, sched.remaining, sched.temp,
             sched.keys, nanmask),
            chunk=(i, n), kind="mixed", rid=slot.req.rid, past=slot.offset,
            rows=n)

    def _decode_tick(self) -> _Tick | None:
        """Prepare one fused decode chunk (no prefill work pending);
        ``None`` when every slot was preempted while growing."""
        sched = self.sched
        if self.chaos is not None and self.chaos.fire("runner.mixed"):
            raise ChaosError("injected decode-chunk failure")
        if sched.lazy:
            sched.grow_for_decode(self.decode_chunk, self.stats)
        sched.check_capacity(self.decode_chunk, self.stats)
        if not sched.num_active:
            return None
        nanmask, _ = self._nan_targets()
        return self._tick(
            self.runner.decode_fn,
            (sched.pages, sched.cur, sched.pos, sched.remaining, sched.temp,
             sched.keys, nanmask), kind="decode")

    def _tick(self, fn, inputs: tuple, chunk=None, **span) -> _Tick:
        """A :class:`_Tick` whose dispatch span also counts the decoding
        slots."""
        span["slots"] = sum(s is not None and s.phase == DECODING
                            for s in self.sched.slots)
        span["tp"] = self.runner.tp
        return _Tick(fn, inputs, self.sched.remaining.copy(), span, chunk)

    def _call(self, tick: _Tick) -> tuple[float, tuple]:
        """Dispatch the tick's executable and read its results back.
        Returns the host seconds of both (the runner's ``prefill_s`` /
        ``decode_s``) and the tokens, their fault flags and the chunk's
        sampled token, key and fault flag, left on the device (empty for a
        decode chunk): :meth:`_finish` reads the flag, and the token and
        key only when the chunk completes its prompt."""
        sched, runner = self.sched, self.runner
        t0 = time.time()
        with TraceAnnotation("engine.dispatch", **tick.span):
            args = (runner.params, runner.caches,
                    *(jnp.asarray(x) for x in tick.inputs))
            self.stats.collective_bytes += runner.psum_bytes(tick.fn, args)
            runner.caches, *out = tick.fn(*args)
        with TraceAnnotation("engine.sync"):
            *chunk_out, cur, pos, remaining, keys, toks, oks = out
            toks, oks = np.asarray(toks), np.asarray(oks)
            sched.cur, sched.pos = np.array(cur), np.array(pos)
            sched.remaining, sched.keys = np.array(remaining), np.array(keys)
        dt = time.time() - t0
        if tick.chunk is None:
            self.stats.decode_s += dt
            self.stats.chunks += 1
        else:
            self.stats.prefill_s += dt
            self.stats.mixed_steps += 1
        return dt, (toks, oks, chunk_out)

    def _finish(self, tick: _Tick, toks, oks, chunk_out):
        """Credit the tick's tokens; on a mixed step, advance the prompt
        and commit it when the chunk completed it."""
        sched = self.sched
        now = self._now()
        self._emit(toks, oks, tick.before, now)
        if tick.chunk is None:
            return
        i, n = tick.chunk
        tok0, key1, chunk_ok = chunk_out
        if not bool(chunk_ok):
            # poisoned prompt chunk: isolate the prefilling request (its
            # pages were never published to the radix tree)
            self.stats.faults_isolated += 1
            sched.retire(i, now, FinishReason.FAULT)
            return
        slot = sched.slots[i]
        slot.offset += n
        if slot.offset == len(slot.req.full_prompt()):
            sched.commit_prefill(i, int(tok0), key1, now, self.stats)

    def _emit(self, toks, oks, before, now: float):
        """Credit decoded tokens to their slots and retire finished ones.
        ``before`` (remaining at tick start) bounds each slot's share — a
        slot that was prefilling or frozen contributes nothing.  A step
        whose ``ok`` flag dropped marks a fault: tokens from that step on
        are discarded and the slot retires FAULT (isolated — the other
        slots' rows are untouched)."""
        for i, slot in enumerate(self.sched.slots):
            if slot is None or before[i] == 0:
                continue
            take = toks[i][: before[i]]
            bad = np.nonzero(~oks[i][: before[i]])[0]
            faulted = bad.size > 0
            if faulted:
                take = take[: bad[0]]
            if self.eos_id is not None:
                stop = np.nonzero(take == self.eos_id)[0]
                if stop.size:
                    take = take[: stop[0] + 1]
            slot.emitted.extend(int(t) for t in take)
            slot.token_times.extend(now for _ in take)
            self.stats.tokens_out += len(take)
            if faulted:
                self.stats.faults_isolated += 1
                self.sched.retire(i, now, FinishReason.FAULT)
            elif self.sched.remaining[i] == 0:
                self.sched.retire(i, now)

    def _schedule(self) -> _Tick | None:
        """Expire deadlines, admit, and prepare the tick: the unified mixed
        step while a prompt is prefilling, else a fused decode-only chunk;
        ``None`` when there is nothing to run."""
        sched = self.sched
        if self.chaos is not None:
            self.chaos.fire("clock.skew")  # may advance the injected clock
        sched.expire(self._now(), self.stats)
        sched.admit(self.runner, self.stats, self.max_prefill_variants)
        self.stats.peak_active = max(self.stats.peak_active, self.num_active)
        try:
            nc = sched.next_chunk()
            if nc is not None:
                return self._mixed_tick(*nc)
            if self.num_active:
                return self._decode_tick()
        except ChaosError:
            pass  # injected transient tick failure: nothing dispatched; retry
        return None

    def step(self) -> list[RequestResult]:
        """One scheduling iteration, in phases the profiler sees as spans:
        ``engine.schedule`` (:meth:`_schedule`), ``engine.dispatch`` and
        ``engine.sync`` (the compiled call and its results read back), and
        ``engine.emit`` (tokens credited, a completed prompt committed and
        published to the radix tree, finished slots retired).  The host
        time outside dispatch and sync adds to ``ServeStats.host_s``.
        Returns newly finished requests (including rejected/cancelled/
        expired ones)."""
        if self._closed:
            raise RuntimeError("engine is closed; create a new Engine")
        sched = self.sched
        t0 = time.time()
        with TraceAnnotation("engine.schedule"):
            tick = self._schedule()
        busy = 0.0
        if tick is not None:
            busy, read = self._call(tick)
            with TraceAnnotation("engine.emit"):
                self._finish(tick, *read)
        if self.radix is not None:
            self.stats.prefix_hit_tokens = self.radix.hit_tokens
            self.stats.prefix_lookup_tokens = self.radix.lookup_tokens
        out, sched.finished = sched.finished, []
        self.stats.host_s += time.time() - t0 - busy
        return out

    def run(self) -> list[RequestResult]:
        """Drive ``step`` until queue and slots drain; returns all results
        (rejected submissions included)."""
        results = []
        while self.sched.queue or self.num_active:
            results.extend(self.step())
        out, self.sched.finished = self.sched.finished, []
        results.extend(out)
        return results

    # ------------------------------------------------------------------
    # batch-generate compatibility surface (seed API)
    # ------------------------------------------------------------------

    def generate(self, prompts: list[list[int]], max_new: int = 32,
                 temperature: float = 0.0, seed: int = 0):
        """Submit a closed batch and run it to completion.  Returns
        ``(sequences, stats)`` like the seed engine: ``sequences[i]`` is
        prompt + generated for ``prompts[i]``."""
        t_stats = ServeStats(prefill_s=-self.stats.prefill_s,
                             decode_s=-self.stats.decode_s,
                             tokens_out=-self.stats.tokens_out,
                             prefills=-self.stats.prefills,
                             chunks=-self.stats.chunks,
                             mixed_steps=-self.stats.mixed_steps)
        rids = [self.submit(p, max_new, temperature, seed=seed * 1000003 + i)
                for i, p in enumerate(prompts)]
        by_rid = {r.rid: r for r in self.run()}
        out = [by_rid[r].tokens for r in rids]
        t_stats.prefill_s += self.stats.prefill_s
        t_stats.decode_s += self.stats.decode_s
        t_stats.tokens_out += self.stats.tokens_out
        t_stats.prefills += self.stats.prefills
        t_stats.chunks += self.stats.chunks
        t_stats.mixed_steps += self.stats.mixed_steps
        t_stats.peak_active = self.stats.peak_active
        t_stats.prefix_hit_tokens = self.stats.prefix_hit_tokens
        t_stats.prefix_lookup_tokens = self.stats.prefix_lookup_tokens
        return out, t_stats
