"""How late the open-loop generator submitted behind its schedule, by the
harness clock: the 99th percentile over the window's submissions."""

from benchmarks.chip import stats


def read(run):
    if not run.lags:
        return None
    return 1e3 * stats.percentile(run.lags, 99)
