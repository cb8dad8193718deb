"""Arithmetic of the end-to-end metrics: a rate is over the whole
window, a tail over every sample, so one stall moves both."""

from types import SimpleNamespace

import pytest

from bench import spec, stats


def steady(n_req=8, period=0.05, n_tok=50, stall_at=None, stall=0.0):
    """Token times of ``n_req`` requests each emitting every ``period``
    s from ``period / 2``; with ``stall_at`` every token after that time
    comes ``stall`` s later."""

    out = []
    for r in range(n_req):
        s = [(k + 0.5) * period for k in range(n_tok)]
        if stall_at is not None:
            s = [t + stall if t > stall_at else t for t in s]
        out.append(s)
    return out


def reader(name):
    return spec.load_module("metrics", name).read


def run_of(stamps, t0, t1, recs=None):
    return SimpleNamespace(stamps=lambda: stamps, t0=t0, t1=t1,
                           seconds=t1 - t0, recs=recs or [])


def test_tokens_counted_only_inside_the_window():
    s = [[0.1, 0.5, 1.0, 1.5, 2.0]]
    assert stats.tokens_in(s, 0.5, 1.5) == 2
    assert stats.token_gaps(s, 0.5, 1.6) == [0.5, 0.5]


def test_tokens_of_requests_begun_before_the_window_count():
    s = [[-1.0, -0.5, 0.25, 0.75]]
    assert stats.tokens_in(s, 0.0, 1.0) == 2
    assert stats.token_gaps(s, 0.0, 1.0) == [0.5]


def test_a_stall_moves_rate_and_tail():
    base = steady()
    hit = steady(stall_at=1.0, stall=1.0)
    rate, tail = reader("output_tok_s"), reader("itl_p99_ms")
    r0, r1 = rate(run_of(base, 0, 2.5)), rate(run_of(hit, 0, 2.5))
    assert r0 == pytest.approx(8 * 50 / 2.5)
    # the stall removes a second of tokens from the window
    assert r1 == pytest.approx(r0 * 1.5 / 2.5, rel=0.05)
    t0, t1 = tail(run_of(base, 0, 2.5)), tail(run_of(hit, 0, 2.5))
    assert t0 == pytest.approx(50.0)
    assert t1 > 500.0


def test_p95_reads_the_ticks_that_prefill():
    """One tick in ten also prefills and takes three times as long: the
    95th percentile of the gaps reads it, the median does not; a stall
    of a second moves the p95 of a long window only where it holds over
    a twentieth of the gaps."""

    ticks = [0.0]
    for k in range(200):
        ticks.append(ticks[-1] + (0.15 if k % 10 == 9 else 0.05))
    stamps = [ticks[1:] for _ in range(8)]
    run = run_of(stamps, 0, ticks[-1] + 0.01)
    assert reader("itl_p95_ms")(run) == pytest.approx(150.0)
    assert stats.percentile(stats.token_gaps(stamps, 0, 99), 50) == \
        pytest.approx(0.05)
    hit = [[t + 1.0 if t > 3.0 else t for t in s] for s in stamps]
    assert reader("itl_p95_ms")(run_of(hit, 0, 99)) == pytest.approx(150.0)
    assert reader("itl_p99_ms")(run_of(hit, 0, 99)) == pytest.approx(150.0)
    assert reader("itl_p95_ms")(run_of([[1.0]], 0.0, 2.0)) is None


def test_first_token_latency_is_from_due():
    recs = [SimpleNamespace(due=0.0, stamps=[0.2, 0.3]),
            SimpleNamespace(due=0.5, stamps=[1.5]),
            SimpleNamespace(due=2.0, stamps=[]),          # none yet
            SimpleNamespace(due=-3.0, stamps=[-1.0, 0.1])]  # before window
    assert stats.first_token_latencies(recs, 0.0, 2.0) == [0.2, 1.0]
    p = reader("ttft_p90_ms")(run_of([], 0.0, 2.0, recs))
    assert p == pytest.approx(1e3 * (0.2 + 0.9 * 0.8))


def test_a_stall_moves_the_first_token_tail():
    recs = [SimpleNamespace(due=0.1 * i, stamps=[0.1 * i + 0.05])
            for i in range(40)]
    base = reader("ttft_p90_ms")(run_of([], 0.0, 5.0, recs))
    late = [SimpleNamespace(due=r.due, stamps=[r.stamps[0] + 1.0])
            if i % 8 == 0 else r for i, r in enumerate(recs)]
    assert base == pytest.approx(50.0)
    assert reader("ttft_p90_ms")(run_of([], 0.0, 5.0, late)) > 500.0


def test_empty_samples_give_nothing():
    assert stats.percentile([], 99) is None
    assert reader("itl_p99_ms")(run_of([[1.0]], 0.0, 2.0)) is None
    assert reader("ttft_p90_ms")(run_of([], 0.0, 2.0)) is None


def test_queue_wait_over_admissions_in_the_window():
    recs = [SimpleNamespace(due=0.0, admitted=0.1),
            SimpleNamespace(due=0.5, admitted=0.9),
            SimpleNamespace(due=-1.0, admitted=-0.5),   # before the window
            SimpleNamespace(due=1.0, admitted=None)]    # still queued
    p = reader("queue_wait_p90_ms")(SimpleNamespace(recs=recs, t0=0.0,
                                                    t1=2.0))
    assert p == pytest.approx(1e3 * (0.1 + 0.9 * 0.3))


def test_slot_occupancy_from_the_program_counters():
    run = SimpleNamespace(batch=8, counters={
        "ticks0": 10, "slot_ticks0": 50, "ticks1": 110, "slot_ticks1": 650})
    assert reader("slot_occupancy")(run) == pytest.approx(75.0)
    assert reader("slot_occupancy")(SimpleNamespace(batch=8,
                                                    counters={})) is None
