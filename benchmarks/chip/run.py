"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process: make the weights on the device from the seed (in their
shards, where the configuration's instance is a tensor-parallel pod over
several chips), deploy them through ``ClusterFrontend.deploy``, warm the
prompt lengths the cell's traffic can send, drive
``ClusterFrontend.submit`` / ``pump`` for ``--seconds``, check the served
tokens against the plain reference, and print one JSON object as the last
line of standard output. With ``--trace 0`` its metrics are the cell's
end-to-end metrics, measured from the client's side; with ``--trace 1``
the window (or its last ``trace.last_seconds``, where the configuration
names them) is traced and the metrics are the cell's per-layer metrics.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import check, peaks, stats, traffic  # noqa: E402
from benchmarks.chip import trace as devtrace  # noqa: E402
from benchmarks.chip.weights import seed_words  # noqa: E402

FN = "bench"
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"
PASS_S = 1e-3       # pump budget: about one dispatch-and-sync pass
WARM_BUDGET_S = 1200.0


class BenchError(Exception):
    """The run cannot produce a result."""


# -- finding the pieces by name ---------------------------------------------


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str) -> Any:
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def adapter(cfg: dict) -> Any:
    return importlib.import_module(
        f"benchmarks.chip.adapters.{cfg['model_type']}")


# -- one served request, seen from the client --------------------------------


@dataclasses.dataclass
class Sent:
    req: Any                 # the program's ServeRequest (None if refused)
    due: float               # when it was due (open) or sent (closed)
    sent: float
    prompt_len: int
    times: list[float] = dataclasses.field(default_factory=list)
    seen: int = 0

    def observe(self, now: float) -> None:
        n = len(self.req.tokens_out)
        if n > self.seen:
            self.times += [now] * (n - self.seen)
            self.seen = n

    @property
    def over(self) -> bool:
        return self.req is None or self.req.done

    @property
    def failed(self) -> bool:
        return self.req is None or self.req.outcome is not None


@dataclasses.dataclass
class RunRecord:
    """Everything a per-layer reader may read."""

    cfg: dict
    seconds: float
    sent: list[Sent]
    end: float
    lags: list[float]        # open loop: submission - due, seconds
    telemetry: dict          # counters' change over the window, summed
    block_size: int
    trace: Optional[dict] = None
    peak: Optional[peaks.Peak] = None
    # Where the trace covers only the window's last part: the host time it
    # began, and the counters' change from then to the close.
    traced_from: Optional[float] = None
    traced_telemetry: Optional[dict] = None

    def times(self, s: Sent) -> list[float]:
        return [t for t in s.times if t <= self.end]


def telemetry(frontend: Any) -> dict:
    out: dict[str, int] = {}
    for eng in frontend.engines:
        for counters in eng.telemetry().values():
            for k, v in counters.items():
                out[k] = out.get(k, 0) + v
    return out


# -- the served path ------------------------------------------------------------


def cell_devices(cell: dict, platform: str) -> list:
    """The devices a cell runs on: the first ``chips`` of the host's, which
    must be of ``platform`` and at least that many."""
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell["chips"]:
        raise BenchError(f"need {cell['chips']} {platform} device(s), found "
                         f"{len(devices)} {devices[0].platform}")
    return devices[:cell["chips"]]


def shards(cfg: dict) -> int:
    """How many chips one instance spans (a tensor-parallel pod)."""
    return cfg["deployment"].get("shards", 1)


def served_model(cfg: dict, seed: int, devices: list) -> tuple[Any, Any]:
    """The program's model for ``cfg`` and its weights from ``seed``: on the
    default device, or drawn straight into the shards of a pod over the
    first ``shards(cfg)`` of ``devices``."""
    from repro.distributed.sharding import tp_mesh
    n = shards(cfg)
    if n > len(devices):
        raise BenchError(f"{cfg['name']} spans {n} chips; the cell has "
                         f"{len(devices)}")
    ad = adapter(cfg)
    model = ad.build(cfg)
    params = ad.make_params(cfg, model, *seed_words(seed),
                            mesh=tp_mesh(n, devices[:n]))
    return model, params


def pod_admission_bytes(params: Any, n: int) -> int:
    """The memory a pod member may be charged for.  The program charges
    every member of a tensor-parallel pod the whole parameter tree
    (``ClusterFrontend.place_instance``), though a member holds only its
    shard of it; the bytes the devices report are raised by the
    difference, so admission still weighs what the fullest member holds,
    its share of the KV pool included, against its memory."""
    from repro.core.model_sharing import pytree_nbytes
    from repro.serving.engine import per_device_bytes
    from repro.serving.frontend import device_mem_bytes, node_devices
    held = max(per_device_bytes(params).values())
    return device_mem_bytes(node_devices(n)) + pytree_nbytes(params) - held


def deploy(cfg: dict, mix: dict, model: Any, params: Any) -> Any:
    from repro.core.resources import Alloc
    from repro.serving.frontend import ClusterFrontend
    dep = cfg["deployment"]
    bs = dep["block_size"]
    max_len = -(-traffic.max_rows(mix) // bs) * bs
    n = shards(cfg)
    frontend = ClusterFrontend(
        n_nodes=n, mem_bytes=pod_admission_bytes(params, n) if n > 1
        else None)
    frontend.deploy(FN, model, params, Alloc(**dep["alloc"]),
                    n_instances=dep["instances"],
                    max_batch=dep["slots_per_instance"], max_len=max_len,
                    batching="paged", block_size=bs,
                    n_kv_blocks=dep.get("kv_blocks"),
                    prefix_sharing=dep["prefix_sharing"], shards=n)
    return frontend


def warm_lengths(mix: dict) -> list[int]:
    """The shortest and longest prompt the mix can send and every power of
    two between them: the lengths the prefill pads to."""
    lo, hi = traffic.min_prompt(mix), traffic.max_prompt(mix)
    out = {lo, hi}
    p = 1
    while p < hi:
        if p > lo:
            out.add(p)
        p *= 2
    return sorted(out)


def warm_up(frontend: Any, cfg: dict, mix: dict) -> None:
    """Compile every program the window will run: each warm length on each
    instance, with a few decode rounds (a round whose inputs came from the round before
    compiles apart from the first)."""
    vocab = cfg["vocab_size"]
    reqs = []
    for n in warm_lengths(mix):
        for _ in range(cfg["deployment"]["instances"]):
            prompt = ((np.arange(n, dtype=np.int64) * 7919 + n) % vocab
                      ).astype(np.int32)
            reqs.append(frontend.submit(FN, prompt, max_new_tokens=4))
    t0 = time.perf_counter()
    while not all(r.done for r in reqs):
        if time.perf_counter() - t0 > WARM_BUDGET_S:
            raise BenchError("warm-up did not finish")
        frontend.pump(budget_s=0.05)
    if any(r.outcome is not None for r in reqs):
        raise BenchError("a warm-up request failed")


def annotate(on: bool) -> Callable[[str], Any]:
    if not on:
        return lambda name: contextlib.nullcontext()
    return jax.profiler.TraceAnnotation


def traced_seconds(cfg: dict, seconds: float) -> Optional[float]:
    """How much of the window's end a traced run profiles, where the
    configuration's ``trace.last_seconds`` asks for less than the whole
    window; None for the whole window."""
    last = cfg.get("trace", {}).get("last_seconds")
    return last if last is not None and last < seconds else None


def serve_window(frontend: Any, plan: traffic.Traffic, seconds: float,
                 span: Callable[[str], Any],
                 traced_s: Optional[float] = None,
                 start_trace: Callable[[], None] = lambda: None
                 ) -> tuple[list[Sent], float, list[float]]:
    """Drive the frontend for ``seconds``; returns what was sent, the end
    of the window and the open loop's submission lags.  The window's span
    covers all of it, or with ``traced_s`` its last ``traced_s`` seconds:
    ``start_trace`` is called as that part begins and the span opens when
    it returns."""
    sent: list[Sent] = []
    live: list[Sent] = []
    lags: list[float] = []
    nxt = 0
    clients: list[Optional[Sent]] = [None] * len(plan.clients)
    queues = [list(reversed(c)) for c in plan.clients]

    def submit(r: traffic.Request, due: float) -> Sent:
        now = time.perf_counter()
        s = Sent(None, due, now, len(r.prompt))
        try:
            s.req = frontend.submit(FN, r.prompt,
                                    max_new_tokens=r.max_new_tokens)
        except (ValueError, KeyError):
            pass  # refused at submission: counted as failed
        sent.append(s)
        if s.req is not None:
            live.append(s)
        return s

    t0 = time.perf_counter()
    end = t0 + seconds
    with contextlib.ExitStack() as window:
        if traced_s is None:
            window.enter_context(span(devtrace.WINDOW))
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if traced_s is not None and now >= end - traced_s:
                traced_s = None
                start_trace()
                window.enter_context(span(devtrace.WINDOW))
            with span("bench.submit"):
                if plan.loop == "open":
                    while (nxt < len(plan.arrivals)
                           and t0 + plan.arrivals[nxt].due_s <= now):
                        due = t0 + plan.arrivals[nxt].due_s
                        lags.append(submit(plan.arrivals[nxt], due).sent
                                    - due)
                        nxt += 1
                else:
                    for c, q in enumerate(queues):
                        if not q or (clients[c] is not None
                                     and not clients[c].over):
                            continue
                        due = (t0 + q[-1].due_s if clients[c] is None
                               else now)
                        if due <= now:
                            clients[c] = submit(q.pop(), due)
            if frontend.has_work():
                with span("bench.pump"):
                    frontend.pump(budget_s=PASS_S, slice_s=PASS_S)
                now = time.perf_counter()
                for s in live:
                    s.observe(now)
                live = [s for s in live if not s.over]
            else:
                wake = end
                if plan.loop == "open" and nxt < len(plan.arrivals):
                    wake = min(end, t0 + plan.arrivals[nxt].due_s)
                elif plan.loop == "closed":
                    wake = min([end] + [t0 + q[-1].due_s for c, q in
                                        enumerate(queues)
                                        if q and clients[c] is None])
                with span("bench.wait"):
                    time.sleep(max(0.0, min(wake - now, 0.002)))
    return sent, end, lags


# -- metrics ------------------------------------------------------------------


def end_to_end(rec: RunRecord) -> dict[str, float]:
    ttft, itl, out = [], [], 0
    for s in rec.sent:
        ts = rec.times(s)
        ttft.append(((ts[0] if ts else rec.end) - s.due) * 1e3)
        itl += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
        out += len(ts)
    if not ttft or not itl:
        raise BenchError("the window served too little to measure")
    return {"ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "itl_p95_ms": stats.percentile(itl, 95),
            "out_tokens_per_s": out / rec.seconds}


def per_layer(rec: RunRecord, metrics: list[dict]) -> dict[str, float]:
    out = {}
    for m in metrics:
        value = load_module("layer_metrics", m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = float(value)
    return out


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


# -- one run --------------------------------------------------------------------


def run_cell(cell: dict, cfg: dict, mix: dict, metrics: list[dict], *,
             seed: int, seconds: float, trace: bool,
             platform: str = "tpu", keep_trace: bool = False,
             log: Callable[[str], None] = lambda m: None,
             compared: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result object (without printing).
    ``metrics`` are the cell's end-to-end metrics, or with ``trace`` its
    per-layer ones.  ``platform`` is the platform the devices must be.
    ``compared``, when given, receives the requests held to the reference
    (``served``) and the gap of each served token (``gaps``)."""
    devices = cell_devices(cell, platform)
    device = devices[0]
    model, params = served_model(cfg, seed, devices)
    frontend = deploy(cfg, mix, model, params)
    warm_up(frontend, cfg, mix)
    plan = traffic.generate(mix, cfg["vocab_size"], seed, seconds)
    compiles = _count_compiles()
    tel0 = telemetry(frontend)
    trace_dir = OUT_DIR / "trace" / f"{cell['name']}-{seed}"
    part = traced_seconds(cfg, seconds) if trace else None
    traced: dict[str, Any] = {}

    def start_trace() -> None:
        t0 = time.perf_counter()
        jax.profiler.start_trace(str(trace_dir))
        traced.update(start=time.perf_counter(), tel=telemetry(frontend))
        log(f"trace started in {traced['start'] - t0:.2f} s")

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        if part is None:
            jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - PROCESS_START
    sent, end, lags = serve_window(frontend, plan, seconds, annotate(trace),
                                   part, start_trace)
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace written in {time.perf_counter() - t0:.1f} s")
    n_compiles = compiles()
    tel1 = telemetry(frontend)
    tel = {k: v - tel0.get(k, 0) for k, v in tel1.items()}
    mems = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    rec = RunRecord(cfg, seconds, sent, end, lags, tel,
                    cfg["deployment"]["block_size"])
    if traced:
        rec.traced_from = traced["start"]
        rec.traced_telemetry = {k: v - traced["tel"].get(k, 0)
                                for k, v in tel1.items()}
    finished = [check.Served(np.asarray(s.req.prompt, np.int32),
                             np.asarray(s.req.tokens_out, np.int32))
                for s in sent if s.req is not None and s.req.done
                and s.req.outcome is None and s.times and s.times[-1] <= end]
    # The program's state is freed before the reference runs.
    del frontend, params, model
    gc.collect()
    out: dict[str, Any] = {}
    if trace:
        t0 = time.perf_counter()
        rec.trace = devtrace.summarize(devtrace.find_xplane(str(trace_dir)))
        log(f"trace read in {time.perf_counter() - t0:.1f} s")
        rec.peak = peaks.peak(device.device_kind)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        values = per_layer(rec, metrics)
    else:
        values = end_to_end(rec)
        values["setup_s"] = setup_s
    served = check.sample(finished, mix["check"]["requests"], seed)
    log(f"compared {len(served)} of {len(finished)} finished requests")
    gaps = (check.served_gaps(cfg, seed, served, mix["check"]["requests"],
                              traffic.max_rows(mix),
                              mix["reply_tokens"]["max"])
            if served else np.zeros(0))
    ok, checks = check.verdict(cfg, gaps, served)
    if compared is not None:
        compared.update(served=served, gaps=gaps)
    units = {m["name"]: m["unit"] for m in metrics}
    out["correct"] = bool(ok)
    out["attempted"] = len(sent)
    out["failed"] = sum(s.failed for s in sent)
    out["metrics"] = {k: {"value": v, "unit": units[k]}
                      for k, v in values.items() if k in units}
    out["device"] = {"platform": device.platform, "kind": device.device_kind,
                     "count": len(jax.devices()),
                     "memory_peak_bytes": max(mems, key=lambda m: m or 0),
                     "memory_peak_bytes_by_device": mems}
    if trace:
        out["device"]["busy_s"] = rec.trace["busy_s"]
        out["device"]["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["compiles_in_window"] = n_compiles
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def _count_compiles() -> Callable[[], int]:
    """Count XLA compiles from now on; the returned call reads the count."""
    from jax import monitoring
    n = [0]

    def listener(event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    monitoring.register_event_duration_secs_listener(listener)
    return lambda: n[0]


def configure_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, whatever the environment names."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler trace under .bench_out/")
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    try:
        bench = load_benchmark()
        cell = find(bench["workloads"], args.workload, "workload")
        cfg = load_json("configs", cell["config"])
        mix = load_json("traffic", cell["traffic"])
        configure_cache()
        result = run_cell(cell, cfg, mix,
                          cell_metrics(bench, cell, bool(args.trace)),
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          keep_trace=args.keep_trace, log=log)
    except BenchError as e:
        log(f"no result: {e}")
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
