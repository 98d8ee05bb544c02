"""Readers of the program's request stamps (``Request.submitted_at``,
``admitted_at``, ``first_token_at``): hand-built cases with known answers,
a program that lacks the stamps, and a tiny open-loop run on the CPU in
which a request's lag, scheduler wait and admission to first token add
up to its time to first token within the step that returned it."""
from types import SimpleNamespace

import pytest

import tiny
from chipbench import harness as H


def reader(name):
    return H.load_reader(name)


def track(due, submitted, sub_at, adm_at, first_at, token_at=None):
    h = SimpleNamespace(submitted_at=sub_at, admitted_at=adm_at,
                        first_token_at=first_at, out_tokens=[])
    return H.Track(req=None, handle=h, due=due, submitted=submitted,
                   tokens=[] if token_at is None else [token_at])


def obs(tracks, t0=0.0, t_end=10.0):
    return H.Obs(cfg={}, chips=1, slots=4, peaks=None, t0=t0, t_end=t_end,
                 setup_s=0.0, tracks=tracks, steps=[])


def test_scheduler_wait_from_stamps():
    o = obs([track(1.0, 1.01, 1.02, 1.12, 1.30),
             track(2.0, 2.0, 2.0, 2.3, 2.5),
             # not admitted by the close: counts with its wait then
             track(9.0, 9.0, 9.5, None, None),
             # due after the window: left out
             track(11.0, 11.0, 11.0, 11.0, 11.1)])
    assert reader("sched_queue_wait_p50_ms")(o) == pytest.approx(300.0)


def test_admission_to_first_token_from_stamps():
    o = obs([track(1.0, 1.0, 1.0, 1.1, 1.2),
             track(2.0, 2.0, 2.0, 2.1, 2.5),
             # admitted, no token by the close: counts with its wait then
             track(9.0, 9.0, 9.0, 9.2, None),
             # never admitted: no admission to count from
             track(9.5, 9.5, 9.5, None, None)])
    assert reader("admit_to_first_token_p50_ms")(o) == pytest.approx(400.0)


def test_program_without_stamps_reads_nothing():
    h = SimpleNamespace(submitted_at=1e9, finished_at=0.0, out_tokens=[])
    o = obs([H.Track(req=None, handle=h, due=1.0, submitted=1.0)])
    assert reader("sched_queue_wait_p50_ms")(o) is None
    assert reader("admit_to_first_token_p50_ms")(o) is None
    assert reader("sched_queue_wait_p50_ms")(obs([])) is None


def test_stamps_add_up_to_ttft(cache_dir, monkeypatch):
    names = ["sched_queue_wait_p50_ms", "admit_to_first_token_p50_ms",
             "queue_wait_p50_ms"]
    seen = []
    load = H.load_reader

    def spy(name):
        read = load(name)
        return lambda o: (seen.append(o), read(o))[1]
    monkeypatch.setattr(H, "load_reader", spy)
    cfg, mix = tiny.fresh(tiny.CONFIG), tiny.fresh(tiny.CHAT)
    res = H.run_cell(tiny.cell(mix), cfg, mix,
                     [{"name": n, "unit": "ms"} for n in names],
                     2**31 + 29, 1.5, False, require_tpu=False)
    assert res["correct"], res["checks"]
    vals = {n: res["metrics"][n]["value"] for n in names}
    assert 0 <= vals["sched_queue_wait_p50_ms"] <= vals["queue_wait_p50_ms"]
    assert vals["admit_to_first_token_p50_ms"] > 0
    o = seen[0]
    steps = {s.t: s for s in o.steps}
    checked = 0
    for t in o.due_in_window():
        h = t.handle
        if not t.tokens:
            continue
        parts = ((t.submitted - t.due) + (h.admitted_at - h.submitted_at)
                 + (h.first_token_at - h.admitted_at))
        ttft = t.tokens[0] - t.due
        assert t.submitted <= h.submitted_at <= h.admitted_at \
            <= h.first_token_at <= t.tokens[0]
        # the harness stamps the token when step() returns, and its
        # submit time just before the program's
        assert 0 <= ttft - parts <= (steps[t.tokens[0]].wall
                                     + h.submitted_at - t.submitted)
        checked += 1
    assert checked >= 10
