"""Find an open-loop cell's knee: the highest of a few fixed rates at which
the backlog does not grow and 90% of requests meet both of the mix's
limits.

    python3 benchmarks/chip/tools/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rates 1.5 2 2.5 3

One process: the weights, the deployment and the warm-up are made once;
then each rate gets a window of ``--seconds`` with its own traffic, and the
served path drains before the next.  Prints one JSON line per rate.  A
request meets the limits when its first token came within ``ttft_ms`` of
its due time and its mean gap between tokens is within ``tpot_ms``, both
over its whole life (the served path drains after each window); one that
failed or never finished misses.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import run, traffic  # noqa: E402


def unfinished(sent: list[run.Sent], t: float) -> int:
    return sum(1 for s in sent if s.sent <= t and not (
        s.req is not None and s.req.done and s.times and s.times[-1] <= t))


def judge(sent: list[run.Sent], end: float, limits: dict) -> float:
    met = 0
    for s in sent:
        ts = [t for t in s.times if t <= end]
        if s.failed or not s.req.done or len(ts) != len(s.req.tokens_out):
            continue
        tpot = (ts[-1] - ts[0]) / max(len(ts) - 1, 1)
        met += ((ts[0] - s.due) * 1e3 <= limits["ttft_ms"]
                and tpot * 1e3 <= limits["tpot_ms"])
    return met / len(sent)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = run.load_benchmark()
    cell = run.find(bench["workloads"], args.workload, "workload")
    cfg = run.load_json("configs", cell["config"])
    mix = run.load_json("traffic", cell["traffic"])
    run.configure_cache()
    model, params = run.served_model(cfg, args.seed,
                                     run.cell_devices(cell, "tpu"))
    frontend = run.deploy(cfg, mix, model, params)
    run.warm_up(frontend, cfg, mix)
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        plan = traffic.generate(m, cfg["vocab_size"], args.seed, args.seconds)
        sent, end, lags = run.serve_window(frontend, plan, args.seconds,
                                           run.annotate(False))
        rec = run.RunRecord(cfg, args.seconds, sent, end, lags, {},
                            cfg["deployment"]["block_size"])
        row = {"rate_per_s": rate, "attempted": len(sent),
               "backlog_mid": unfinished(sent, end - args.seconds / 2),
               "backlog_end": unfinished(sent, end),
               **run.end_to_end(rec)}
        # Drain, still watching tokens, so every request is judged on its
        # whole life and not cut at the close.
        t0 = time.perf_counter()
        while frontend.has_work() and time.perf_counter() - t0 < 300:
            frontend.pump(budget_s=run.PASS_S, slice_s=run.PASS_S)
            now = time.perf_counter()
            for s in sent:
                if s.req is not None:
                    s.observe(now)
        row["met_both"] = judge(sent, float("inf"), mix["limits"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
