"""Where the device's idle time went, by the serving path's own spans,
and the device time of each compiled program.

The program writes ``serve.*`` spans from inside
``ServingEngine.pump`` (``serve.schedule``, ``serve.quota_wait``,
``serve.pass`` holding ``serve.admit``, ``serve.cow``, ``serve.dispatch``,
``serve.sync`` and ``serve.land``) into the profiler's trace, on the clock the
profiler aligns with the device's.  This module reduces a trace with
them:

- ``modules``: executions and device seconds per program in the window,
  a program named by its module with the profiler's ``(<id>)`` cut off,
  so ``jit_decode_paged_tok(123...)`` reads ``jit_decode_paged_tok``;
- ``pass_host_s``: for each ``serve.pass`` in the window, its length less
  the ``serve.sync`` spans inside it: the pass's host time outside the
  wait for the device;
- ``idle_by_span``: device-idle seconds in the window by the innermost
  host span over them (a ``serve.*`` span, else a ``bench.*`` one, else
  ``no host span``), longest first;
- ``idle_gaps``: the gaps ``trace.summarize`` lists, as it names them;
- ``pump_idle_s``: device-idle seconds inside ``bench.pump``, and the
  part of them that a ``serve.*`` span holds;
- ``sync_lag_s``: for each ``serve.sync``, its end less the end of the
  last device program before it, which is the program it waited for:
  the host's wake-up after the device finished plus the offset between
  the two clocks.  Device programs show up about 1.0-1.2 ms before the
  host call that launched them on a v5e (``tests/data``), so each idle
  gap lies that much earlier on the host clock than it did: the first
  millisecond or so after a ``serve.sync`` is put down to it.

Without ``serve.*`` spans (a program that writes none) ``idle_by_span``
holds the harness spans alone.

    python3 benchmarks/chip/tools/attribute_idle.py <trace dir>
"""

from __future__ import annotations

import bisect

from jax.profiler import ProfileData

from benchmarks.chip import trace as devtrace

PASS = "serve.pass"
SYNC = "serve.sync"
PUMP = "bench.pump"


def program(module: str) -> str:
    """``jit_decode_paged_tok(4950857752023182173)`` ->
    ``jit_decode_paged_tok``."""
    return module.split("(", 1)[0]


def modules(plane, lo: float, hi: float) -> dict[str, list[float]]:
    """{program: [executions, device ns]} of the plane's ``XLA Modules``
    events that overlap [lo, hi), their time clipped to it."""
    out: dict[str, list[float]] = {}
    line = devtrace._line(plane, "XLA Modules")
    for ev in (line.events if line is not None else ()):
        d = min(ev.end_ns, hi) - max(ev.start_ns, lo)
        if d > 0:
            acc = out.setdefault(program(ev.name), [0, 0.0])
            acc[0] += 1
            acc[1] += d
    return out


def pass_host_s(spans: list[tuple[float, float, str]], lo: float,
                hi: float) -> list[float]:
    """Seconds of each ``serve.pass`` that starts in [lo, hi), less the
    ``serve.sync`` spans inside it, in the order the passes ran."""
    syncs = sorted((s, e) for s, e, n in spans if n == SYNC)
    out = []
    for s, e, n in sorted(spans):
        if n != PASS or not lo <= s < hi:
            continue
        waited = sum(min(b, e) - max(a, s) for a, b in syncs
                     if a < e and b > s)
        out.append((e - s - waited) * 1e-9)
    return out


def sync_lag_s(spans: list[tuple[float, float, str]], plane, lo: float,
               hi: float) -> list[float]:
    """For each ``serve.sync`` that ends in [lo, hi), seconds from the end
    of the last device program that ended before it to its end."""
    line = devtrace._line(plane, "XLA Modules")
    ends = sorted(ev.end_ns for ev in (line.events if line else ()))
    out = []
    for _, e, n in sorted(spans):
        k = bisect.bisect_right(ends, e)
        if n == SYNC and lo <= e < hi and k:
            out.append((e - ends[k - 1]) * 1e-9)
    return out


def summarize(path: str, top: int = 10) -> dict:
    """``modules`` ({program: [executions, device seconds]}, device
    seconds a mean over the device planes), ``pass_host_s``,
    ``idle_by_span`` and ``idle_gaps`` (each ``[name, seconds]``, longest
    first), ``pump_idle_s`` ({"all", "serve"} seconds) and ``sync_lag_s``
    (on the first device plane)."""
    pd = ProfileData.from_file(path)
    spans = devtrace.host_spans(pd)
    windows = [(s, e) for s, e, n in spans if n == devtrace.WINDOW]
    if not windows:
        raise ValueError(f"no {devtrace.WINDOW} span in {path}")
    lo, hi = windows[0]
    planes = devtrace.device_planes(pd)
    if not planes:
        raise ValueError(f"no device plane with XLA Ops in {path}")
    progs: dict[str, list[float]] = {}
    for i, plane in enumerate(planes):
        for name, (n, t) in modules(plane, lo, hi).items():
            acc = progs.setdefault(name, [0, 0.0])
            acc[0] += n if i == 0 else 0
            acc[1] += t / len(planes)
    # The idle gaps of the first device plane, as trace.summarize finds
    # them.
    union = devtrace._clip(devtrace.merge(
        (s, e) for s, e, _ in devtrace.device_ops(planes[0])), lo, hi)
    edges = [lo] + [x for iv in union for x in iv] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    by_span: dict[str, float] = {}
    pump = {"all": 0.0, "serve": 0.0}
    for acc in devtrace.attribute(gaps, spans):
        for (bench, serve), t in acc.items():
            name = devtrace.span_name((bench, serve))
            by_span[name] = by_span.get(name, 0.0) + t
            if bench == PUMP:
                pump["all"] += t
                pump["serve"] += t if serve else 0.0
    return {
        "modules": {n: [c, t * 1e-9] for n, (c, t) in sorted(
            progs.items(), key=lambda kv: -kv[1][1])},
        "pass_host_s": pass_host_s(spans, lo, hi),
        "idle_by_span": [[n, t * 1e-9] for n, t in sorted(
            by_span.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": devtrace.idle_gaps(gaps, spans, top),
        "pump_idle_s": {k: v * 1e-9 for k, v in pump.items()},
        "sync_lag_s": sync_lag_s(spans, planes[0], lo, hi),
    }
