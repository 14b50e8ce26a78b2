"""Median wait in an instance's queue, by the program's own stamps:
``ServeRequest.admitted_at - submitted_at`` (the engine clock) over the
requests sent in the window.  A request not admitted by the close counts
with its wait to the close, as ``ttft_p50_ms`` counts a request with no
token.  None where the program stamps no admissions."""

from benchmarks.chip import stats


def read(run):
    sent = [s for s in run.sent if s.req is not None]
    if not sent or not hasattr(sent[0].req, "admitted_at"):
        return None
    waits = []
    for s in sent:
        to_close = run.end - s.sent
        r = s.req
        waits.append(to_close if r.admitted_at is None
                     else min(r.admitted_at - r.submitted_at, to_close))
    return 1e3 * stats.percentile(waits, 50)
