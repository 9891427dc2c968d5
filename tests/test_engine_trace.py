"""What the engine reports about its own time and its requests: the phase
spans of ``Engine.step`` on the profiler's clock (``engine.schedule``,
``engine.dispatch``, ``engine.sync``, ``engine.emit``), the host-time
counter ``ServeStats.host_s`` that covers the same boundaries, and each
request's admission record (``admit_s``, ``prefix_hit``)."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config, reduce_config
from repro.models import model as M
from repro.serving import Engine, EngineConfig, FinishReason

PHASES = ["engine.schedule", "engine.dispatch", "engine.sync", "engine.emit"]


@pytest.fixture(scope="module")
def olmo():
    cfg = reduce_config(get_config("olmo-1b"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _econ(**kw):
    kw.setdefault("max_len", 96)
    kw.setdefault("page_size", 16)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("chunk_tokens", 16)
    return EngineConfig(**kw)


def _shared_prompts(cfg, n=3, seed=0):
    """Prompts behind one 20-token prefix: a full page and 4 rows of the
    next, so later admissions hit the radix cache and copy a page."""
    rng = np.random.RandomState(seed)
    head = rng.randint(1, cfg.vocab_size, 20).tolist()
    return [head + rng.randint(1, cfg.vocab_size, 12 + 5 * i).tolist()
            for i in range(n)]


def _drain(eng, on_step=None):
    res = {}
    while eng.num_queued or eng.num_active:
        if on_step is not None:
            on_step()
        res.update((r.rid, r) for r in eng.step())
    return res


def _host_spans(logdir) -> list:
    """(start, end, name, args) of the ``engine.`` and ``test.`` spans of
    the newest trace under ``logdir``, by start."""
    path = sorted(glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "test.")):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


@pytest.fixture(scope="module")
def traced(olmo, tmp_path_factory):
    """Three requests behind a shared prefix served under the profiler,
    each ``Engine.step`` inside a ``test.step`` span: the spans, the
    results, and each step's counter deltas."""
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=3))
    prompts = _shared_prompts(cfg)
    eng.generate([list(range(1, 30))], max_new=6)  # compile, untraced
    rids = [eng.submit(p, max_new=6) for p in prompts]
    logdir = tmp_path_factory.mktemp("trace")
    host0 = eng.stats.host_s
    steps, res = [], {}
    with jax.profiler.trace(str(logdir)):
        while eng.num_queued or eng.num_active:
            s0 = (eng.stats.mixed_steps, eng.stats.chunks)
            with TraceAnnotation("test.step"):
                out = eng.step()
            steps.append((eng.stats.mixed_steps - s0[0],
                          eng.stats.chunks - s0[1]))
            res.update((r.rid, r) for r in out)
    return dict(spans=_host_spans(logdir), steps=steps,
                results=[res[r] for r in rids], prompts=prompts,
                host_s=eng.stats.host_s - host0)


def _phases_by_step(spans):
    outer = [s for s in spans if s[2] == "test.step"]
    inner = [s for s in spans if s[2].startswith("engine.")]
    return [[s for s in inner if a <= s[0] and s[1] <= b]
            for a, b, _, _ in outer]


def test_every_tick_holds_its_four_phases_in_order(traced):
    by_step = _phases_by_step(traced["spans"])
    assert len(by_step) == len(traced["steps"])
    kinds = []
    for phases, (mixed, chunks) in zip(by_step, traced["steps"]):
        names = [s[2] for s in phases]
        if not (mixed or chunks):
            assert names == ["engine.schedule"]
            continue
        assert names == PHASES
        for (_, e0, _, _), (s1, _, _, _) in zip(phases, phases[1:]):
            assert e0 <= s1  # one after the other, none nested
        args = phases[1][3]
        kinds.append(args["kind"])
        assert args["kind"] == ("mixed" if mixed else "decode")
        assert "slots" in args
        assert args["tp"] == 1  # no mesh: no tensor parallelism
        if mixed:
            assert {"rid", "past", "rows"} <= set(args)
    assert {"mixed", "decode"} <= set(kinds)


def test_dispatch_spans_name_each_prompt_chunk(traced):
    """The mixed steps' ``rid``/``past``/``rows`` walk each prompt from its
    radix hit to its end, one chunk after the other; ``slots`` counts the
    decoding slots beside them."""
    dispatch = [s[3] for s in traced["spans"] if s[2] == "engine.dispatch"]
    for r, p in zip(traced["results"], traced["prompts"]):
        chunks = [(d["past"], d["rows"]) for d in dispatch
                  if d["kind"] == "mixed" and d["rid"] == r.rid]
        assert chunks[0][0] == r.prefix_hit
        for (past, rows), (nxt, _) in zip(chunks, chunks[1:]):
            assert nxt == past + rows
        assert chunks[-1][0] + chunks[-1][1] == len(p)
    decode = [d for d in dispatch if d["kind"] == "decode"]
    assert decode and all(1 <= d["slots"] <= 3 for d in decode)
    assert dispatch[0]["slots"] == 0  # the first prompt runs alone


def test_host_s_agrees_with_the_schedule_and_emit_spans(traced):
    spent = sum(b - a for a, b, name, _ in traced["spans"]
                if name in ("engine.schedule", "engine.emit")) * 1e-9
    assert spent > 0
    assert traced["host_s"] == pytest.approx(spent, rel=0.10)


def test_admission_lies_between_arrival_and_first_token(traced):
    for r in traced["results"]:
        assert r.ok
        assert r.arrival_s <= r.admit_s <= r.first_token_s


def test_prefix_hit_is_the_admissions_radix_delta(olmo):
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=3))
    rids = [eng.submit(p, max_new=4) for p in _shared_prompts(cfg, 4)]
    seen, res = {}, {}
    while eng.num_queued or eng.num_active:
        hit0 = eng.radix.hit_tokens
        res.update((r.rid, r) for r in eng.step())
        for s in eng._slots:  # admitted in this step: the step's delta
            if s is not None and s.req.rid not in seen:
                seen[s.req.rid] = eng.radix.hit_tokens - hit0
    hits = [res[r].prefix_hit for r in rids]
    assert hits == [seen[r] for r in rids]
    assert hits[0] == 0 and all(h == 20 for h in hits[1:])  # page + COW rows


def test_readmission_keeps_the_first_admission(olmo):
    """A request preempted under page pressure and admitted again keeps
    the time and the radix hit of its first admission."""
    cfg, params = olmo
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, 16).tolist() for _ in range(2)]
    eng = Engine(cfg, params, _econ(max_len=64, max_batch=2, n_pages=4,
                                    prefix_cache=False,
                                    preemption="recompute"))
    rids = [eng.submit(p, max_new=20) for p in prompts]
    first, reqs = {}, {}

    def watch():
        for s in eng._slots:
            if s is not None:
                reqs[s.req.rid] = s.req
                first.setdefault(s.req.rid, s.req.admit_s)

    res = _drain(eng, watch)
    assert eng.stats.preempted >= 1
    again = [r for r in rids if reqs[r].preemptions]
    assert again
    for rid in rids:
        assert res[rid].ok
        assert res[rid].admit_s == first[rid]
        assert res[rid].prefix_hit == 0
        assert res[rid].arrival_s <= res[rid].admit_s \
            <= res[rid].first_token_s


def test_a_request_never_admitted_has_no_admission(olmo):
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=1, max_queue=1))
    eng.submit([1, 2, 3], max_new=2)
    eng.submit([4, 5, 6], max_new=2)  # the queue is full: rejected
    out = eng.step() + eng.close()    # the first admitted, then cancelled
    by = {r.finish_reason: r for r in out}
    assert by[FinishReason.REJECTED].admit_s is None
    assert by[FinishReason.REJECTED].prefix_hit is None
    eng = Engine(cfg, params, _econ(max_batch=1))
    eng.submit([1, 2, 3], max_new=2)
    (r,) = eng.close()                # cancelled while queued
    assert r.finish_reason == FinishReason.CANCELLED and r.admit_s is None
    assert r.prefix_hit is None


def test_tokens_per_s_counts_mixed_steps_too(olmo):
    cfg, params = olmo
    eng = Engine(cfg, params, _econ(max_batch=2))
    eng.generate(_shared_prompts(cfg, 2), max_new=5)
    s = eng.stats
    assert s.prefill_s > 0 and s.decode_s > 0 and s.host_s > 0
    assert s.tokens_per_s == pytest.approx(
        s.tokens_out / (s.prefill_s + s.decode_s))
