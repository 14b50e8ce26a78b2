"""Where the device's idle time went, by the serving path's own spans,
and the device time of each compiled program.

``trace.py`` names an idle gap after the harness span (``bench.*``) that
overlaps it most.  The program also writes ``serve.*`` spans from inside
``ServingEngine.pump`` (``serve.schedule``, ``serve.quota_wait``,
``serve.pass`` holding ``serve.admit``, ``serve.cow``, ``serve.dispatch``,
``serve.sync`` and ``serve.land``) into the same trace, on the clock the
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
- ``idle_gaps``: the gaps ``trace.summarize`` lists, with the same
  seconds, each name followed by the ``serve.*`` span that holds most of
  the gap (``bench.pump>serve.land``);
- ``pump_idle_s``: device-idle seconds inside ``bench.pump``, and the
  part of them that a ``serve.*`` span holds;
- ``sync_lag_s``: for each ``serve.sync``, its end less the end of the
  last device program before it, which is the program it waited for:
  the host's wake-up after the device finished plus the offset between
  the two clocks.  Device programs show up about 1.0-1.2 ms before the
  host call that launched them on a v5e (``tests/data``), so each idle
  gap lies that much earlier on the host clock than it did: the first
  millisecond or so after a ``serve.sync`` is put down to it.

Without ``serve.*`` spans (a program that writes none) the gaps read as
in ``trace.py`` and ``idle_by_span`` holds the harness spans alone.

    python3 benchmarks/chip/tools/attribute_idle.py <trace dir>
"""

from __future__ import annotations

import bisect
from typing import Optional

from jax.profiler import ProfileData

from benchmarks.chip import trace as devtrace

SERVE = "serve."
PASS = "serve.pass"
SYNC = "serve.sync"
PUMP = "bench.pump"
NO_SPAN = "no host span"


def host_spans(pd: ProfileData) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every ``bench.*`` and ``serve.*`` span
    on the host planes."""
    return [(ev.start_ns, ev.end_ns, ev.name)
            for p in pd.planes if not p.name.startswith("/device:")
            for line in p.lines for ev in line.events
            if ev.name.startswith((devtrace.HOST_PREFIX, SERVE))]


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


def _innermost(open_spans: dict[int, tuple[float, str]], prefix: str
               ) -> Optional[str]:
    best = None
    for start, name in open_spans.values():
        if name.startswith(prefix) and name != devtrace.WINDOW and (
                best is None or start >= best[0]):
            best = (start, name)
    return None if best is None else best[1]


def attribute(intervals: list[tuple[float, float]],
              spans: list[tuple[float, float, str]]
              ) -> list[dict[tuple[Optional[str], Optional[str]], float]]:
    """For each of the disjoint ``intervals`` (sorted), its ns by the
    innermost (``bench.*``, ``serve.*``) span pair open over them; a side
    with no span open is None.  Spans on one thread nest, so the
    innermost open span is the one that started last."""
    events = sorted([(s, 1, k) for k, (s, _, _) in enumerate(spans)]
                    + [(e, 0, k) for k, (_, e, _) in enumerate(spans)])
    open_spans: dict[int, tuple[float, str]] = {}

    def apply(ev: tuple[float, int, int]) -> None:
        _, starts, k = ev
        if starts:
            open_spans[k] = (spans[k][0], spans[k][2])
        else:
            open_spans.pop(k, None)

    out = []
    j = 0
    for lo, hi in intervals:
        while j < len(events) and events[j][0] <= lo:
            apply(events[j])
            j += 1
        acc: dict[tuple[Optional[str], Optional[str]], float] = {}
        t = lo
        while True:
            nxt = events[j][0] if j < len(events) and events[j][0] < hi \
                else hi
            if nxt > t:
                key = (_innermost(open_spans, devtrace.HOST_PREFIX),
                       _innermost(open_spans, SERVE))
                acc[key] = acc.get(key, 0.0) + (nxt - t)
                t = nxt
            if nxt >= hi:
                break
            apply(events[j])
            j += 1
        out.append(acc)
    return out


def summarize(path: str, top: int = 10) -> dict:
    """``modules`` ({program: [executions, device seconds]}, device
    seconds a mean over the device planes), ``pass_host_s``,
    ``idle_by_span`` and ``idle_gaps`` (each ``[name, seconds]``, longest
    first), ``pump_idle_s`` ({"all", "serve"} seconds) and ``sync_lag_s``
    (on the first device plane)."""
    pd = ProfileData.from_file(path)
    spans = host_spans(pd)
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
    by_gap = attribute(gaps, spans)
    by_span: dict[str, float] = {}
    pump = {"all": 0.0, "serve": 0.0}
    for acc in by_gap:
        for (bench, serve), t in acc.items():
            name = serve or bench or NO_SPAN
            by_span[name] = by_span.get(name, 0.0) + t
            if bench == PUMP:
                pump["all"] += t
                pump["serve"] += t if serve else 0.0
    others = [s for s in spans if s[2] != devtrace.WINDOW]
    named = []
    for k in sorted(range(len(gaps)),
                    key=lambda k: gaps[k][0] - gaps[k][1])[:top]:
        s, e = gaps[k]
        name = devtrace._blame(s, e, others)
        serve: dict[str, float] = {}
        for (_, sv), t in by_gap[k].items():
            if sv is not None:
                serve[sv] = serve.get(sv, 0.0) + t
        if serve:
            name += ">" + max(serve, key=serve.get)
        named.append([name, (e - s) * 1e-9])
    return {
        "modules": {n: [c, t * 1e-9] for n, (c, t) in sorted(
            progs.items(), key=lambda kv: -kv[1][1])},
        "pass_host_s": pass_host_s(spans, lo, hi),
        "idle_by_span": [[n, t * 1e-9] for n, t in sorted(
            by_span.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
        "pump_idle_s": {k: v * 1e-9 for k, v in pump.items()},
        "sync_lag_s": sync_lag_s(spans, planes[0], lo, hi),
    }
