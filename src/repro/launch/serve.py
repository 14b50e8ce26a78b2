"""End-to-end FaST-GShare serving driver (live data plane on this host).

Deploys N weight-shared instances of one or more architectures (reduced
configs — real JAX executors on CPU) onto a ServingEngine node, gates every
step through the FaST-Manager token scheduler, drives a batched request
load, and reports throughput / latency / utilization / occupancy and the
model-sharing memory ledger.

Usage:
  PYTHONPATH=src python -m repro.launch.serve \
      --arch qwen2-7b --arch rwkv6-1.6b --instances 2 --requests 32
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.model_sharing import pytree_nbytes
from repro.core.resources import Alloc
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="repeatable; reduced config of each arch is served")
    ap.add_argument("--instances", type=int, default=2,
                    help="instances per function (share one weight copy)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--sm", type=float, default=0.24,
                    help="spatial share per instance")
    ap.add_argument("--quota", type=float, default=0.5)
    ap.add_argument("--quota-limit", type=float, default=1.0)
    ap.add_argument("--window", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    archs = args.arch or ["qwen2-7b"]

    engine = ServingEngine(window=args.window)
    rng = np.random.default_rng(args.seed)
    alloc = Alloc(sm=args.sm, quota_request=args.quota,
                  quota_limit=args.quota_limit)

    unshared_total = 0
    for arch in archs:
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(args.seed))
        nbytes = pytree_nbytes(params)
        unshared_total += nbytes * args.instances
        engine.deploy(arch, model, params, alloc,
                      n_instances=args.instances,
                      max_batch=args.max_batch,
                      max_len=args.prompt_len + args.max_new_tokens + 1)
        print(f"[deploy] {arch}: {args.instances} instances sharing "
              f"{nbytes / 1e6:.1f} MB of weights "
              f"({cfg.n_layers}L d={cfg.d_model})")

    reqs = []
    for i in range(args.requests):
        fn = archs[i % len(archs)]
        prompt = rng.integers(
            0, get_config(fn, reduced=True).vocab_size,
            size=args.prompt_len).astype(np.int32)
        reqs.append(engine.submit(fn, prompt,
                                  max_new_tokens=args.max_new_tokens))

    t0 = time.perf_counter()
    done = engine.pump(budget_s=120.0)
    wall = time.perf_counter() - t0

    print(f"\n[serve] completed {done}/{len(reqs)} requests in {wall:.2f}s "
          f"({done / max(wall, 1e-9):.1f} req/s)")
    for fn, rec in engine.recorders.items():
        if rec.count():
            print(f"  {fn:24s} n={rec.count():4d}  p50={rec.p50():.3f}s  "
                  f"p99={rec.p99():.3f}s")
    sched = engine.scheduler
    print(f"[manager] utilization={sched.utilization(last_n=50):.2f}  "
          f"occupancy={sched.occupancy(last_n=50):.2f}  "
          f"(window={args.window}s)")
    shared = engine.memory_bytes()
    print(f"[model sharing] weights resident: {shared / 1e6:.1f} MB shared "
          f"vs {unshared_total / 1e6:.1f} MB unshared "
          f"({1 - shared / max(unshared_total, 1):.0%} saved)")
    sample = reqs[0]
    print(f"[sample] req0 prompt[:8]={sample.prompt[:8].tolist()} -> "
          f"tokens_out={sample.tokens_out}")


if __name__ == "__main__":
    main()
