"""Tests for FaST-Manager's multi-token scheduler (paper §3.3.2)."""

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.core.manager import TokenScheduler, fair_share_baseline
from repro.core.resources import Alloc


def alloc(sm, q_req, q_lim=None):
    return Alloc(sm=sm, quota_request=q_req, quota_limit=q_lim or q_req)


def test_priority_by_q_miss_descending():
    ts = TokenScheduler(window=1.0)
    ts.register("low", alloc(0.2, 0.2))
    ts.register("high", alloc(0.2, 0.8))
    ts.register("mid", alloc(0.2, 0.5))
    for p in ("low", "high", "mid"):
        ts.request_token(p, 0.0)
    granted = [t.pod_id for t in ts.dispatch(0.0)]
    assert granted == ["high", "mid", "low"]  # descending Q_miss


def test_sm_global_limit_blocks_head_of_queue():
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.6, 0.9))
    ts.register("b", alloc(0.5, 0.8))  # would exceed 100% with a
    ts.register("c", alloc(0.3, 0.7))  # would fit, but queue blocks at head
    for p in ("a", "b", "c"):
        ts.request_token(p, 0.0)
    granted = [t.pod_id for t in ts.dispatch(0.0)]
    # Paper: the adapter returns tokens "until it encounters
    # S_SMs + S_running > 100%" — head-of-line blocking, no skip-ahead.
    assert granted == ["a"]
    assert ts.sm_running() == pytest.approx(0.6)


def test_quota_limit_blocks_until_next_window():
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.5, 0.3, 0.5))
    ts.request_token("a", 0.0)
    assert len(ts.dispatch(0.0)) == 1
    ts.complete("a", elapsed=0.55, now=0.55)  # Q_used 0.55 > Q_limit 0.5
    ts.request_token("a", 0.56)
    assert ts.dispatch(0.56) == []  # blocked: Q_remain <= 0
    ts.request_token("a", 1.01)  # next window: quota reset
    assert len(ts.dispatch(1.01)) == 1


def test_elastic_quota_between_request_and_limit():
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.5, 0.3, 0.8))
    ts.request_token("a", 0.0)
    ts.dispatch(0.0)
    ts.complete("a", elapsed=0.4, now=0.4)  # past request, under limit
    ts.request_token("a", 0.4)
    assert len(ts.dispatch(0.4)) == 1  # elastic: still schedulable


def test_completion_without_token_raises():
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.5, 0.5))
    with pytest.raises(RuntimeError):
        ts.complete("a", 0.1, 0.0)


def test_utilization_and_occupancy_accounting():
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.25, 1.0))
    for w in range(4):
        ts.request_token("a", float(w))
        ts.dispatch(float(w))
        ts.complete("a", elapsed=0.5, now=w + 0.5)
    ts.dispatch(4.0)  # roll final window
    assert ts.utilization(last_n=4) == pytest.approx(0.5)
    assert ts.occupancy(last_n=4) == pytest.approx(0.5 * 0.25)


def test_fair_share_baseline_equal_slices():
    shares = fair_share_baseline({"a": alloc(0.2, 0.5), "b": alloc(0.9, 0.9)})
    assert shares == {"a": 0.5, "b": 0.5}


@settings(max_examples=50, deadline=None)
@given(
    st.lists(
        st.tuples(st.floats(0.05, 1.0), st.floats(0.05, 0.95)),
        min_size=1, max_size=8,
    )
)
def test_dispatch_never_exceeds_sm_global_limit(pods):
    """Property: at no point does Σ running SM shares exceed 100%."""
    ts = TokenScheduler(window=1.0)
    for i, (sm, q) in enumerate(pods):
        ts.register(f"p{i}", alloc(round(sm, 2), round(q, 2)))
        ts.request_token(f"p{i}", 0.0)
    ts.dispatch(0.0)
    assert ts.sm_running() <= 1.0 + 1e-9
    # Complete in arbitrary order and re-request; limit must still hold.
    t = 0.1
    for i in range(len(pods)):
        pid = f"p{i}"
        if ts.pods[pid].holding is not None:
            ts.complete(pid, 0.05, t)
            ts.request_token(pid, t)
            ts.dispatch(t)
            assert ts.sm_running() <= 1.0 + 1e-9
            t += 0.05


@settings(max_examples=30, deadline=None)
@given(st.floats(0.1, 0.9), st.integers(2, 6))
def test_quota_isolation_property(q_limit, n_windows):
    """Property: a pod can never consume more than Q_limit + one-step
    overshoot per window (token granularity = one step, like kernel bursts)."""
    q_limit = round(q_limit, 2)
    step = 0.05
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.5, min(q_limit, 0.9), q_limit))
    now = 0.0
    per_window: dict[int, float] = {}
    while now < n_windows:
        ts.request_token("a", now)
        if ts.dispatch(now):
            per_window[int(now)] = per_window.get(int(now), 0.0) + step
            ts.complete("a", step, min(now + step, n_windows))
        now += step
        now = round(now, 10)
    for w, used in per_window.items():
        assert used <= q_limit + step + 1e-9


def test_token_wait_accrues_while_quota_blocked():
    """Virtual time: a pod that used its whole limit by t=0.2 asks again
    at once and is held until the window rolls at t=1.0 — 0.8 s of
    token wait.  A repeated request keeps its first time."""
    ts = TokenScheduler(window=1.0)
    ts.register("a", alloc(0.5, 0.2, 0.2))
    ts.request_token("a", 0.0)
    assert len(ts.dispatch(0.0)) == 1
    ts.complete("a", elapsed=0.2, now=0.2)  # Q_used 0.2 = Q_limit
    ts.request_token("a", 0.2)
    assert ts.dispatch(0.2) == []
    ts.request_token("a", 0.6)  # the engine re-requests every loop turn
    assert ts.dispatch(0.6) == []
    assert len(ts.dispatch(1.0)) == 1  # window rolled: quota reset
    assert ts.pods["a"].token_wait_s == pytest.approx(0.8)
