"""Bring-up smoke run of the served path on TPU chips.

Drives ``ClusterFrontend.submit`` -> ``ServingEngine.pump`` ->
``FunctionInstance`` once, at the published widths of Qwen2-7B with
random bf16 weights made from ``--seed``, and checks what comes out.
Run it from the root of a checkout:

    python chip_smoke.py              # one chip: two weight-sharing
                                      # instances, paged KV, prefix sharing
    python chip_smoke.py --chips 4    # four chips: replicas behind the
                                      # router (with a migration) and a
                                      # tensor-parallel pod, each against
                                      # the one-chip instance

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The lines before it (compile and wall time, time to first token, tokens
per second, peak device bytes) are bring-up information, not benchmark
results.  A failed check exits non-zero without that line, and so does a
host on which JAX finds no TPU.

The phases are functions of a ``SmokeConfig``, so the tests run them at a
tiny size on CPU devices.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from typing import Any, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import qwen2_7b  # noqa: E402
from repro.core.resources import Alloc  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    serve_pspec, tp_mesh, tree_shardings)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving.frontend import ClusterFrontend  # noqa: E402

FN = "qwen2-7b"
# Two instances share one chip side by side in the MRA packer.
SHARED_ALLOC = Alloc(sm=0.5, quota_request=0.5, quota_limit=1.0)
# Both sides of the rectangle above 0.5: no two replicas fit on one node,
# so each takes its own chip.
REPLICA_ALLOC = Alloc(sm=0.6, quota_request=0.6, quota_limit=1.0)
POD_SHARDS = 4


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def qwen2_7b_cut(n_layers: int = 16) -> ModelConfig:
    """Qwen2-7B at its published widths, cut in depth only.

    16 of 28 layers are 9.64 GB of bf16 weights.  A v5e lets a program
    use 15.75 GB, so that leaves room for the two instances' paged KV
    pools and the float32 reference's upcast embedding (2.2 GB).  A
    decode step updates its pool in place (the layer scan of
    ``transformer.decode_step_paged`` carries it), so it holds no second
    copy.
    """
    return dataclasses.replace(qwen2_7b.config(), n_layers=n_layers,
                               name=f"qwen2-7b-{n_layers}l")


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    model: ModelConfig
    seed: int = 0
    n_requests: int = 16
    prompt_len: int = 512
    prefix_len: int = 256          # shared by the first half of the requests
    new_tokens: tuple[int, int] = (32, 64)  # inclusive range per request
    max_batch: int = 8             # decode slots per instance
    n_instances: int = 2
    block_size: int = 16
    ref_steps: int = 4             # paged decode steps held to the reference
    # Relative L2 error of a served logits row against the float32
    # reference.  bf16 keeps 8 significant bits (unit roundoff 2^-9, about
    # 2e-3); the served path rounds activations to bf16 at about ten
    # points per layer, so over 16 layers independent rounding errors grow
    # to about sqrt(160) * 2e-3 = 2.5e-2.  The limit is twice that, and
    # far below the ~1.4 that logits of a wrong token or position give.
    tol: float = 5e-2

    @property
    def max_len(self) -> int:
        rows = self.prompt_len + self.new_tokens[1] - 1
        return -(-rows // self.block_size) * self.block_size


def make_params(model, seed: int, shardings: Any = None) -> Any:
    """Random weights made on the device(s), never on the host."""
    return jax.jit(model.init, out_shardings=shardings)(jax.random.key(seed))


def make_traffic(cfg: SmokeConfig) -> list[tuple[np.ndarray, int]]:
    """``(prompt, max_new_tokens)`` pairs; the first half share a prefix."""
    rng = np.random.default_rng(cfg.seed)
    vocab = cfg.model.vocab_size
    prefix = rng.integers(0, vocab, cfg.prefix_len)
    lo, hi = cfg.new_tokens
    traffic = []
    for i in range(cfg.n_requests):
        prompt = rng.integers(0, vocab, cfg.prompt_len).astype(np.int32)
        if i < cfg.n_requests // 2:
            prompt[:cfg.prefix_len] = prefix
        traffic.append((prompt, int(rng.integers(lo, hi + 1))))
    return traffic


def deploy(frontend: ClusterFrontend, cfg: SmokeConfig, model, params,
           alloc: Alloc, n: int, shards: int = 1) -> list[str]:
    return frontend.deploy(FN, model, params, alloc, n_instances=n,
                           max_batch=cfg.max_batch, max_len=cfg.max_len,
                           batching="paged", block_size=cfg.block_size,
                           shards=shards)


def submit(frontend: ClusterFrontend, traffic) -> list:
    return [frontend.submit(FN, p, max_new_tokens=n) for p, n in traffic]


def pump_until_done(frontend: ClusterFrontend, reqs: list,
                    budget_s: float = 900.0, poll_s: float = 0.05
                    ) -> tuple[float, list[Optional[float]]]:
    """Pump until every request is done; returns the wall time and each
    request's time to first token, both from the call (the poll period
    bounds the TTFT resolution)."""
    t0 = time.perf_counter()
    first: list[Optional[float]] = [None] * len(reqs)
    while not all(r.done for r in reqs):
        if time.perf_counter() - t0 > budget_s:
            raise SmokeFailure(f"requests still running after {budget_s} s")
        frontend.pump(budget_s=poll_s)
        now = time.perf_counter() - t0
        for i, r in enumerate(reqs):
            if first[i] is None and r.tokens_out:
                first[i] = now
    return time.perf_counter() - t0, first


def check_served(reqs: list, traffic, vocab: int) -> None:
    """Every request completes with its token count, every id in vocab."""
    errors = []
    for i, (r, (_, n)) in enumerate(zip(reqs, traffic)):
        if not r.done or r.outcome is not None or len(r.tokens_out) != n:
            errors.append(f"request {i}: done={r.done} outcome={r.outcome} "
                          f"tokens={len(r.tokens_out)}/{n}")
        if any(not 0 <= t < vocab for t in r.tokens_out):
            errors.append(f"request {i}: token id outside [0, {vocab})")
    if errors:
        raise SmokeFailure("; ".join(errors))


def instances(frontend: ClusterFrontend) -> list:
    return [inst for eng in frontend.engines
            for inst in eng.instances.values()]


def prefill_logits(inst, prompt: np.ndarray) -> np.ndarray:
    """Last-position logits of the instance's own bucketed prefill."""
    return np.asarray(inst._prefill_one(prompt)[0][0], np.float32)


def rel_err(a: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Relative L2 error of each row of ``a`` against ``ref``."""
    return np.linalg.norm(a - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


def reference_errors(cfg: SmokeConfig, model, params,
                     prompt: np.ndarray) -> np.ndarray:
    """Prefill and then ``ref_steps`` greedy paged decode steps of one
    prompt, each logits row against ``Model.forward`` in float32.

    The reference upcasts the embedding, so every activation is float32
    and every matmul runs at ``highest`` precision; the bf16 weights are
    exact in float32 and are upcast one layer at a time inside the scan,
    so no float32 copy of the whole stack is made.  Returns the relative
    L2 error of each row (prefill first) over the real vocabulary.
    """
    vocab, bs, steps = cfg.model.vocab_size, cfg.block_size, cfg.ref_steps
    n = len(prompt)
    n_slots = -(-(n + steps) // bs)
    max_len = n_slots * bs
    logits, entry = jax.jit(
        lambda p, t, k: model.prefill(p, t, max_len=max_len, length=k))(
            params, jnp.asarray(prompt[None]), jnp.int32(n))
    table = jnp.arange(1, n_slots + 1, dtype=jnp.int32)  # 0: null block
    cache = jax.jit(model.append_paged)(
        model.init_paged_cache(n_slots + 1, bs), entry, table)
    step = jax.jit(model.decode_step_paged)
    rows, tokens = [np.asarray(logits[0], np.float32)], []
    for i in range(steps):
        tokens.append(int(np.argmax(rows[-1][:vocab])))
        logits, cache = step(params, jnp.asarray(tokens[-1:], jnp.int32),
                             cache, table[None],
                             jnp.asarray([n + i], jnp.int32))
        rows.append(np.asarray(logits[0], np.float32))
    del cache
    seq = np.concatenate([prompt, tokens]).astype(np.int32)[None]
    ref_params = dict(params, embed=params["embed"].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: model.forward(p, t, train=False)[0])(
            ref_params, seq)
    ref = np.asarray(ref[0, n - 1:n + steps, :vocab], np.float32)
    return rel_err(np.stack(rows)[:, :vocab], ref)


def serve_one_node(cfg: SmokeConfig, model, params, traffic
                   ) -> tuple[ClusterFrontend, list, dict]:
    """Deploy ``n_instances`` weight-sharing instances on one node, warm
    the executors with one request, then serve ``traffic``."""
    frontend = ClusterFrontend(n_nodes=1)
    deploy(frontend, cfg, model, params, SHARED_ALLOC, cfg.n_instances)
    warm = [(np.arange(cfg.prompt_len, dtype=np.int32)
             % cfg.model.vocab_size, 2)]
    warm_s, _ = pump_until_done(frontend, submit(frontend, warm))
    reqs = submit(frontend, traffic)
    wall, first = pump_until_done(frontend, reqs)
    check_served(reqs, traffic, cfg.model.vocab_size)
    n_tokens = sum(len(r.tokens_out) for r in reqs)
    info = {"compile_s": warm_s, "wall_s": wall,
            "ttft_p50_s": float(np.median(first)),
            "ttft_max_s": float(max(first)),
            "tokens_per_s": n_tokens / wall,
            "shared_block_hits": sum(i.shared_block_hits
                                     for i in instances(frontend))}
    return frontend, reqs, info


def phase_one_chip(cfg: SmokeConfig) -> dict:
    """The served path on one device, checked against float32."""
    model = build_model(cfg.model)
    params = make_params(model, cfg.seed)
    traffic = make_traffic(cfg)
    frontend, _, info = serve_one_node(cfg, model, params, traffic)
    if info["shared_block_hits"] == 0:
        raise SmokeFailure("no prompt block was served from the prefix "
                           "cache")
    del frontend  # frees the KV pools before the reference runs
    gc.collect()
    errs = reference_errors(cfg, model, params, traffic[0][0])
    info["ref_rel_err"] = [float(e) for e in errs]
    if not np.all(errs <= cfg.tol):
        raise SmokeFailure(f"served logits vs float32 reference: relative "
                           f"errors {errs} exceed {cfg.tol}")
    stats = jax.devices()[0].memory_stats() or {}
    info["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return info


def phase_four_chips(cfg: SmokeConfig) -> dict:
    """Replicas behind the router and a tensor-parallel pod, each held to
    the one-chip instance.

    Replicas run the one-chip program on other chips, so their tokens
    must equal the one-chip instance's exactly, a migrated request's
    included.  The pod re-tiles its column-parallel matmuls across chips;
    its prefill logits are held to ``cfg.tol`` and its token agreement is
    reported.
    """
    devices = jax.devices()
    if len(devices) < POD_SHARDS:
        raise SmokeFailure(f"need {POD_SHARDS} devices, have {len(devices)}")
    model = build_model(cfg.model)
    traffic = make_traffic(cfg)
    probe = traffic[0][0]
    half = len(traffic) // 2
    info: dict = {}

    # The one-chip reference (device 0).
    params = make_params(model, cfg.seed)
    frontend, reqs, _ = serve_one_node(cfg, model, params, traffic)
    ref_tokens = [list(r.tokens_out) for r in reqs]
    ref_logits = prefill_logits(instances(frontend)[0], probe)
    del frontend, reqs
    gc.collect()

    # Replicas: three on three nodes, one of them migrated to the fourth
    # mid-decode, then a fourth replica on the node it left.
    fe = ClusterFrontend(n_nodes=POD_SHARDS)
    handles = deploy(fe, cfg, model, params, REPLICA_ALLOC, 3)
    reqs = submit(fe, traffic[:half])
    src_node, src_id = handles[0].split(":", 1)
    (target,) = set(range(POD_SHARDS)) - {fe.node_of(h) for h in handles}
    src = fe.engines[int(src_node)].instances[src_id]
    t0 = time.perf_counter()
    while not any(r is not None and r.tokens_out for r in src.slots):
        if time.perf_counter() - t0 > 600:
            raise SmokeFailure("no request started decoding before the "
                               "migration")
        fe.pump(budget_s=1e-3, slice_s=1e-3)  # about one pass per node
    moved = sum(r is not None for r in src.slots)
    if fe.migrate(FN, handles[0], model, params, target=target) is None:
        raise SmokeFailure(f"migration from node {src_node} to node "
                           f"{target} was refused")
    deploy(fe, cfg, model, params, REPLICA_ALLOC, 1)
    reqs += submit(fe, traffic[half:])
    pump_until_done(fe, reqs)
    check_served(reqs, traffic, cfg.model.vocab_size)
    homes = []
    for eng in fe.engines:
        for inst in eng.instances.values():
            held = set(inst.hbm_bytes_by_device())
            if held != {eng.device.id}:
                raise SmokeFailure(f"{inst.inst_id} holds bytes on devices "
                                   f"{sorted(held)}, not its node's "
                                   f"{eng.device.id}")
            homes.append(eng.device.id)
    if sorted(homes) != sorted(d.id for d in devices[:POD_SHARDS]):
        raise SmokeFailure(f"replicas on devices {homes}, not one per chip")
    same = [list(r.tokens_out) == t for r, t in zip(reqs, ref_tokens)]
    if not all(same):
        raise SmokeFailure(f"replica tokens differ from the one-chip "
                           f"instance for requests "
                           f"{[i for i, s in enumerate(same) if not s]}")
    info["replica_devices"] = homes
    info["migrated_requests"] = moved
    del fe, reqs, src, params
    gc.collect()

    # Tensor-parallel pod over all four chips.
    mesh = tp_mesh(POD_SHARDS, devices=devices[:POD_SHARDS])
    params = make_params(model, cfg.seed, tree_shardings(
        model.param_names(), model.abstract_params(), mesh,
        resolver=serve_pspec))
    fe = ClusterFrontend(n_nodes=POD_SHARDS)
    deploy(fe, cfg, model, params, SHARED_ALLOC, 1, shards=POD_SHARDS)
    del params
    (pod,) = instances(fe)
    held = pod.hbm_bytes_by_device()
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(pod.params))
    if len(held) != POD_SHARDS or max(held.values()) >= 0.6 * total:
        raise SmokeFailure(f"pod members hold {held} bytes; expected "
                           f"{POD_SHARDS} devices below 0.6 x {total}")
    err = float(rel_err(prefill_logits(pod, probe)[:cfg.model.vocab_size],
                        ref_logits[:cfg.model.vocab_size]))
    if err > cfg.tol:
        raise SmokeFailure(f"pod prefill logits vs one chip: relative error "
                           f"{err} exceeds {cfg.tol}")
    reqs = submit(fe, traffic)
    pump_until_done(fe, reqs)
    check_served(reqs, traffic, cfg.model.vocab_size)
    info["pod_bytes_by_device"] = held
    info["pod_prefill_rel_err"] = err
    info["pod_identical_streams"] = sum(
        list(r.tokens_out) == t for r, t in zip(reqs, ref_tokens))
    return info


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = SmokeConfig(model=qwen2_7b_cut(), seed=args.seed)
    phase = phase_one_chip if args.chips == 1 else phase_four_chips
    try:
        info = phase(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    for key, value in info.items():
        print(f"{key}: {value}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
