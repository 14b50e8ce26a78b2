"""CPU tests of the span reductions (``spans.py``) and of the per-layer
readers of the serving path's own counters.

    python -m pytest benchmarks/chip/tests -q

``data/serve_trace.xplane.pb`` was recorded on a v5e chip by
``tools/record_serve_trace.py``: three ``bench.pump`` calls, each idling
3 ms outside any ``serve.*`` span and then running one ``serve.pass``
(the program ``jit_decode_round`` dispatched, waited for under
``serve.sync``, then 5 ms idle under ``serve.land``), each followed by
10 ms under ``bench.wait``.  ``data/small_trace.xplane.pb`` is the
fixture ``trace.py`` was checked on, with harness spans alone.
"""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import run, spans
from benchmarks.chip import trace as devtrace

DATA = Path(__file__).parent / "data"
SMALL = str(DATA / "small_trace.xplane.pb")
SERVE = str(DATA / "serve_trace.xplane.pb")
MS = 1e-3


@pytest.fixture(scope="module")
def serve_summary():
    return spans.summarize(SERVE)


# -- the first fixture: what trace.py reads is unchanged ---------------------


def test_the_first_fixture_reduces_as_it_did():
    s = devtrace.summarize(SMALL)
    assert s["busy_s"] == pytest.approx(2.5265e-05, rel=1e-9)
    assert s["window_s"] == pytest.approx(0.03310801, rel=1e-9)
    assert [n for n, _ in s["device_ops"]] == [
        "jit__lambda(4950857752023182173)/%convolution_tanh_fusion",
        "jit__lambda(4950857752023182173)/%copy-start",
        "jit__lambda(4950857752023182173)/%copy-done"]
    assert [t for _, t in s["device_ops"]] == pytest.approx(
        [2.5233e-05, 2.6e-08, 6e-09], rel=1e-9)
    assert [t for _, t in s["idle_gaps"]] == pytest.approx(
        [0.011917648, 0.010823471, 0.010341621, 2e-09, 1e-09, 1e-09, 1e-09],
        rel=1e-9)


def test_spans_keep_trace_py_gaps_without_serve_spans():
    s = spans.summarize(SMALL)
    assert s["idle_gaps"] == devtrace.summarize(SMALL)["idle_gaps"]
    assert s["pass_host_s"] == []
    assert s["pump_idle_s"]["serve"] == 0.0
    assert [n for n, _ in s["idle_by_span"]] == ["bench.wait", "bench.pump",
                                                 "no host span"]
    assert s["modules"] == {"jit__lambda": [2, pytest.approx(2.5277e-05)]}


# -- the reductions on made-up spans -------------------------------------------


def test_idle_goes_to_the_innermost_span():
    spans_ = [(0, 100, "bench.window"), (10, 60, "bench.pump"),
              (20, 50, "serve.pass"), (30, 40, "serve.sync"),
              (70, 90, "bench.wait")]
    acc = devtrace.attribute([(0, 12), (25, 35), (45, 75)], spans_)
    assert acc[0] == {(None, None): 10, ("bench.pump", None): 2}
    assert acc[1] == {("bench.pump", "serve.pass"): 5,
                      ("bench.pump", "serve.sync"): 5}
    assert acc[2] == {("bench.pump", "serve.pass"): 5,
                      ("bench.pump", None): 10, (None, None): 10,
                      ("bench.wait", None): 5}
    # A gap is named by the innermost span that holds most of it.
    gaps = devtrace.idle_gaps([(0, 12), (28, 45), (55, 90)], spans_, top=2)
    assert gaps == [["bench.wait", pytest.approx(35e-9)],
                    ["serve.sync", pytest.approx(17e-9)]]


def test_pass_host_time_leaves_out_the_sync():
    spans_ = [(0, 10_000_000, "serve.pass"),
              (2_000_000, 9_000_000, "serve.sync"),
              (20_000_000, 25_000_000, "serve.pass"),
              (30_000_000, 31_000_000, "serve.pass")]
    assert spans.pass_host_s(spans_, 0, 30_000_000) == pytest.approx(
        [3 * MS, 5 * MS])
    assert spans.program("jit_decode_paged_tok(4950857752023182173)") \
        == "jit_decode_paged_tok"


# -- the chip fixture with serve.* spans ---------------------------------------


def test_programs_are_named_and_timed(serve_summary):
    count, seconds = serve_summary["modules"]["jit_decode_round"]
    assert count == 3
    assert 0 < seconds < devtrace.summarize(SERVE)["busy_s"] * 1.001


def _durations(name):
    return [e - s for s, e, n in devtrace.host_spans(ProfileData.from_file(
        SERVE)) if n == name]


def test_pass_host_time_leaves_out_the_device_wait(serve_summary):
    passes = serve_summary["pass_host_s"]
    assert len(passes) == 3
    lands = _durations("serve.land")
    for host, land, dispatch in zip(passes, lands,
                                    _durations("serve.dispatch")):
        assert host == pytest.approx((land + dispatch) * 1e-9, abs=0.1 * MS)


def test_idle_is_put_down_to_serve_spans(serve_summary):
    by_span = dict(serve_summary["idle_by_span"])
    # The device is idle through every serve.land and bench.wait.
    assert by_span["serve.land"] == pytest.approx(
        sum(_durations("serve.land")) * 1e-9, rel=1e-3)
    assert by_span["bench.wait"] == pytest.approx(
        sum(_durations("bench.wait")) * 1e-9, rel=1e-3)
    assert by_span["bench.pump"] == pytest.approx(9 * MS, rel=0.3)
    pump = serve_summary["pump_idle_s"]
    assert pump["serve"] == pytest.approx(sum(
        t for n, t in serve_summary["idle_by_span"]
        if n.startswith("serve.")))
    assert pump["serve"] / pump["all"] > 0.5
    # The three longest gaps run from a serve.land (5 ms) into the
    # harness's sleep (10 ms), the fourth is the pump's idle outside any
    # serve.* span; trace.py lists and names them alike.
    names = [n for n, _ in serve_summary["idle_gaps"]]
    assert names[:4] == ["bench.wait"] * 3 + ["bench.pump"]
    assert serve_summary["idle_gaps"] == devtrace.summarize(SERVE)["idle_gaps"]


def test_a_sync_ends_soon_after_the_program_it_waited_for(serve_summary):
    """Host and device clocks agree to a few milliseconds: each
    ``serve.sync`` ends 1-2.5 ms after the end of the device program it
    waited for, by a lag that barely moves (the clock offset, mostly)."""
    lags = serve_summary["sync_lag_s"]
    assert len(lags) == 3
    assert all(1 * MS <= t <= 2.5 * MS for t in lags)
    assert max(lags) - min(lags) < 0.5 * MS


# -- readers of the program's counters ------------------------------------------


def _record(reqs, telemetry=None):
    sent = [run.Sent(req=r, due=t, sent=t, prompt_len=4) for t, r in reqs]
    return run.RunRecord(cfg={"deployment": {"instances": 2}},
                         seconds=10.0, sent=sent, end=10.0, lags=[],
                         telemetry=telemetry or {}, block_size=16)


def test_queue_wait_counts_unadmitted_requests_to_the_close():
    read = run.load_module("layer_metrics", "queue_wait_p50_ms").read

    def req(submitted, admitted):
        return SimpleNamespace(submitted_at=submitted, admitted_at=admitted)

    # Engine clock 100 s ahead of the harness's; window [0, 10).
    rec = _record([(1.0, req(101.0, 101.05)), (2.0, req(102.0, 102.2)),
                   (9.0, req(109.0, None)), (9.5, req(109.5, 110.5)),
                   (3.0, req(103.0, 103.01))])
    # ms: the third waits to the close; the fourth is admitted after it.
    waits = [50.0, 200.0, 1000.0, 500.0, 10.0]
    assert read(rec) == pytest.approx(sorted(waits)[2])
    # A program that stamps no admissions: nothing to read.
    assert read(_record([(1.0, SimpleNamespace(submitted_at=1.0))])) is None
    assert read(_record([])) is None


def test_token_wait_share_is_over_instance_seconds():
    read = run.load_module("layer_metrics", "token_wait_share").read
    assert read(_record([], {"token_wait_s": 0.5})) == pytest.approx(2.5)
    assert read(_record([], {"steps": 10})) is None
