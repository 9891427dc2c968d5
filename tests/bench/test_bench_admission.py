"""The harness's rebuilding of each admission against the program's own
record, on the tiny chat run: the radix hit that ``_replay`` infers from
counter deltas equals the ``prefix_hit`` the engine reports, the admission
that ``bench/phases.py`` reads from the steps holds the engine's
``admit_s``, and the steps' host time is the engine's ``host_s``."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

sys.path.insert(0, str(Path(__file__).resolve().parent))
import _tiny  # noqa: E402

FIRST = _tiny.SPEC["workloads"][0]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The tiny run's record, the engine's results by rid, and the engine's
    ``host_s`` when it closed."""
    import jax

    from bench import run as R
    from bench import serve
    from bench.registry import Registry

    root = tmp_path_factory.mktemp("bench")
    name = _tiny.make(root, FIRST["config"], FIRST["traffic"],
                      like=FIRST["name"])
    seen = {}
    drive = serve.drive

    def spy(eng, *a, **kw):
        """``serve.drive`` with the engine's results kept by rid."""
        results = {}

        def keep(fn):
            def call():
                out = fn()
                results.update((r.rid, r) for r in out)
                return out
            return call

        eng.step, eng.close = keep(eng.step), keep(eng.close)
        host0 = eng.stats.host_s
        seen["record"] = drive(eng, *a, **kw)
        seen["results"] = results
        seen["host_s"] = eng.stats.host_s - host0
        return seen["record"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve, "drive", spy)
        R.run_cell(Registry(root), name, 2**33 + 7, 2.0, False,
                   jax.devices()[:1], kernel_mode="reference",
                   t_start=time.time())
    assert seen["record"].replay_error is None
    return seen


def test_replayed_hits_equal_the_programs(served):
    record, results = served["record"], served["results"]
    assert len(results) == len(record.sent)
    admitted = [s for s in record.sent
                if results[s.rid].admit_s is not None]
    assert admitted and any(s.hit for s in admitted)
    for s in record.sent:
        assert s.hit == results[s.rid].prefix_hit, s.idx


def test_admission_step_holds_the_programs_admit_time(served):
    from bench import phases

    record, results = served["record"], served["results"]
    admitted = phases.admissions(record)
    ends = {}
    for st in record.steps:
        if st.chunk is not None:
            ends.setdefault(st.chunk[0], st.t1)
    assert admitted
    for s in record.sent:
        admit_s = results[s.rid].admit_s
        assert (admit_s is None) == (s.idx not in admitted), s.idx
        if admit_s is not None:
            assert admitted[s.idx] <= admit_s <= ends[s.idx], s.idx


def test_steps_host_time_is_the_programs(served):
    """Every step's interval less its dispatch-to-sync time, summed, is the
    engine's ``host_s`` plus the harness's clock reads around the step."""
    steps = served["record"].steps
    harness = sum(s.t1 - s.t0 - s.d["prefill_s"] - s.d["decode_s"]
                  for s in steps)
    assert served["host_s"] <= harness
    assert harness - served["host_s"] <= 0.1 * harness + 1e-4 * len(steps)
