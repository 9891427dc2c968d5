"""The readers of ``bench/phases.py`` on a hand-built record: admission is
the start of the step that computed a request's first prompt chunk, waits
of requests never admitted run until the run gave up, and every reader
returns ``None`` where the record has nothing to read."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import phases  # noqa: E402
from bench.serve import COUNTERS, Record, Sent, Step  # noqa: E402

W = (10.0, 20.0)


def sent(idx, due, submit, first=None, finish="length"):
    s = Sent(idx, [1] * 8, 4, due)
    s.submit_s, s.first_token_s, s.finish = submit, first, finish
    return s


def step(t0, t1, chunk=None, mixed=0, chunks=0, busy=0.0):
    d = dict.fromkeys(COUNTERS, 0)
    d.update(mixed_steps=mixed, chunks=chunks)
    d["prefill_s" if mixed else "decode_s"] = busy
    return Step(t0, t1, d, chunk)


def run_of(sent_, steps, replay_error=None):
    return SimpleNamespace(record=Record(
        sent_, steps, W, {}, 90.0, (0, 0), replay_error=replay_error))


def _run():
    """Request 0 admitted at once, 1 behind it, 2 never admitted, 3 due
    before the window."""
    reqs = [sent(0, 11.0, 11.0, first=11.9), sent(1, 11.2, 11.2, first=13.0),
            sent(2, 12.0, 12.0, finish="cancelled"),
            sent(3, 9.0, 9.0, first=9.5)]
    steps = [step(9.1, 9.5, (3, 0, 8), mixed=1, busy=0.39),
             step(11.0, 11.5, (0, 0, 4), mixed=1, busy=0.49),
             step(11.5, 11.9, (0, 4, 4), mixed=1, busy=0.39),
             step(12.0, 12.5, (1, 2, 6), mixed=1, busy=0.48),
             step(12.5, 13.0, chunks=1, busy=0.49),
             step(13.0, 13.001)]  # nothing ran
    return run_of(reqs, steps)


def test_admission_is_the_first_chunks_step():
    assert phases.admissions(_run().record) == {3: 9.1, 0: 11.0, 1: 12.0}


def test_queue_wait_counts_the_never_admitted_until_give_up():
    waits = phases.queue_waits(_run().record)
    assert waits == pytest.approx([0.0, 0.8, 78.0])
    assert phases.queue_wait_p90_ms(_run()) == pytest.approx(78_000.0)


def test_prefill_runs_from_admission_to_first_token():
    assert phases.prefills(_run().record) == pytest.approx([0.9, 1.0])
    assert phases.prefill_p90_ms(_run()) == pytest.approx(1_000.0)
    failed = _run()
    failed.record.sent[1].finish = "fault"
    assert phases.prefills(failed.record) == pytest.approx([0.9, 78.0])


def test_step_host_time_is_the_step_less_its_call():
    # window steps: 0.01 + 0.01 + 0.02 + 0.01 + 0.001 over 4 calls
    assert phases.step_host_ms(_run()) == pytest.approx(51.0 / 4)


@pytest.mark.parametrize("reader", [phases.queue_wait_p90_ms,
                                    phases.prefill_p90_ms,
                                    phases.step_host_ms])
def test_nothing_to_read_is_none(reader):
    assert reader(run_of([], [])) is None
    if reader is not phases.step_host_ms:
        r = _run()
        r.record.replay_error = "admitted while a prompt prefilled"
        assert reader(r) is None
