"""Where a request's first token and a step's host time go, read from the
harness's own log of every step (``bench/serve.py``): no field of the
program is read, so a program that reports no admission times reads the
same.

A request is admitted in the step that computes its first prompt chunk:
admission holds while a prompt is prefilling, and the mixed step of the
step that admitted a prompt computes its first rows.  Its admission time
is that step's start, early by at most the step's schedule phase (the
expiry and the admissions before it).  Each function returns ``None``
where the run has nothing to read.
"""
from __future__ import annotations

from bench import stats


def admissions(record) -> dict[int, float] | None:
    """Start of the step that admitted each request, by ``Sent.idx``;
    ``None`` where the steps' prompt chunks could not be rebuilt."""
    if record.replay_error:
        return None
    out: dict[int, float] = {}
    for step in record.steps:
        if step.chunk is not None:
            out.setdefault(step.chunk[0], step.t0)
    return out


def queue_waits(record) -> list[float] | None:
    """Admission minus submit time of every request due in the window; a
    request never admitted counts its wait until the run gave up."""
    admitted = admissions(record)
    if admitted is None:
        return None
    return [admitted.get(s.idx, max(record.give_up_s, s.submit_s))
            - s.submit_s
            for s in stats.due_in_window(record.sent, record.window)
            if s.submit_s is not None]


def prefills(record) -> list[float] | None:
    """First-token minus admission time of every request due in the window
    and admitted; one that delivered no token, or failed, counts its wait
    until the run gave up."""
    admitted = admissions(record)
    if admitted is None:
        return None
    out = []
    for s in stats.due_in_window(record.sent, record.window):
        t = admitted.get(s.idx)
        if t is None:
            continue
        end = max(record.give_up_s, t) if stats.failed(s) \
            else s.first_token_s
        out.append(end - t)
    return out


def step_host_s(record) -> float | None:
    """Host seconds per compiled call of the steps that start in the
    window: each step's interval less its dispatch-to-sync time (the
    program's ``prefill_s`` and ``decode_s``), over its mixed steps and
    decode chunks."""
    steps = [s for s in record.steps if stats.in_window(s.t0, record.window)]
    ticks = sum(s.d["mixed_steps"] + s.d["chunks"] for s in steps)
    if not ticks:
        return None
    return sum(s.t1 - s.t0 - s.d["prefill_s"] - s.d["decode_s"]
               for s in steps) / ticks


def _p90_ms(xs):
    return 1e3 * stats.percentile(xs, 90) if xs else None


def queue_wait_p90_ms(run):
    return _p90_ms(queue_waits(run.record))


def prefill_p90_ms(run):
    return _p90_ms(prefills(run.record))


def step_host_ms(run):
    s = step_host_s(run.record)
    return None if s is None else 1e3 * s
