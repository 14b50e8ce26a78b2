"""Print where a traced run's device-idle time went, by host span, and
the device time per program, as one JSON object.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds 50 --trace 1 --keep-trace
    python3 benchmarks/chip/tools/attribute_idle.py \
        .bench_out/trace/<cell>-<n>

Besides ``spans.summarize``: ``ms_per_execution`` of each program,
``pass_host_p50_ms`` and ``sync_lag_ms`` (the medians of ``pass_host_s``
and of ``sync_lag_s``, whose lists are dropped from the output) and
``idle_s``, the window's device-idle seconds.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import spans, stats  # noqa: E402
from benchmarks.chip import trace as devtrace  # noqa: E402


def main(path: str) -> None:
    if os.path.isdir(path):
        path = devtrace.find_xplane(path)
    out = spans.summarize(path)
    base = devtrace.summarize(path)
    passes = out.pop("pass_host_s")
    lags = out.pop("sync_lag_s")
    out["passes"] = len(passes)
    out["pass_host_p50_ms"] = (1e3 * stats.percentile(passes, 50)
                               if passes else None)
    out["sync_lag_ms"] = 1e3 * stats.percentile(lags, 50) if lags else None
    out["ms_per_execution"] = {n: 1e3 * t / c
                               for n, (c, t) in out["modules"].items()}
    out["busy_s"] = base["busy_s"]
    out["window_s"] = base["window_s"]
    out["idle_s"] = base["window_s"] - base["busy_s"]
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
