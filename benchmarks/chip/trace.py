"""From a profiler trace (``.xplane.pb``) to the device's busy time, its
idle gaps, its time in collectives and the operations that took longest.

Only ``jax.profiler.ProfileData`` is used.  A device plane is one named
``/device:<PLATFORM>:<n>``; its operations are the events of its
``XLA Ops`` line, each placed in the ``XLA Modules`` event (the compiled
program) that contains it.  Busy time is the union of the operation
intervals; on several device planes (a cell that spans chips) busy and
collective seconds are the mean over the planes, and ``devices`` says how
many there were.  The window is the host span ``bench.window``, which the
harness opens at the first timed submission and closes at the end.  Each
idle gap inside it is named after the innermost host span (a ``serve.*``
span of the program, else a ``bench.*`` span of the harness) that holds
most of it.  Host and device clocks in a trace agree to about a
millisecond (the recorded fixture in ``tests/data`` shows the device 1.1
ms behind), which is small beside the window and the gaps that matter.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, Optional

from jax.profiler import ProfileData

WINDOW = "bench.window"
HOST_PREFIX = "bench."
SERVE_PREFIX = "serve."
NO_SPAN = "no host span"
# HLO collectives, their asynchronous ``-start``/``-done`` halves included.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def device_planes(pd: ProfileData) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and _line(p, "XLA Ops") is not None]


def merge(intervals: Iterable[tuple[float, float]]
          ) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: list[tuple[float, float]], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def host_spans(pd: ProfileData) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every ``bench.*`` and ``serve.*`` span
    on the host planes."""
    return [(ev.start_ns, ev.end_ns, ev.name)
            for p in pd.planes if not p.name.startswith("/device:")
            for line in p.lines for ev in line.events
            if ev.name.startswith((HOST_PREFIX, SERVE_PREFIX))]


def _innermost(open_spans: dict[int, tuple[float, str]], prefix: str
               ) -> Optional[str]:
    best = None
    for start, name in open_spans.values():
        if name.startswith(prefix) and name != WINDOW and (
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
                key = (_innermost(open_spans, HOST_PREFIX),
                       _innermost(open_spans, SERVE_PREFIX))
                acc[key] = acc.get(key, 0.0) + (nxt - t)
                t = nxt
            if nxt >= hi:
                break
            apply(events[j])
            j += 1
        out.append(acc)
    return out


def span_name(pair: tuple[Optional[str], Optional[str]]) -> str:
    """The innermost span of a (``bench.*``, ``serve.*``) pair: the
    program's span where one is open, else the harness's."""
    bench, serve = pair
    return serve or bench or NO_SPAN


def idle_gaps(gaps: list[tuple[float, float]],
              spans: list[tuple[float, float, str]], top: int
              ) -> list[list]:
    """The ``top`` longest of the disjoint ``gaps`` (sorted), longest
    first, each ``[name, seconds]`` named by the innermost host span that
    holds most of it."""
    longest = sorted(sorted(gaps, key=lambda g: g[0] - g[1])[:top])
    out = []
    for (s, e), acc in zip(longest, attribute(longest, spans)):
        by_name: dict[str, float] = {}
        for pair, t in acc.items():
            by_name[span_name(pair)] = by_name.get(span_name(pair), 0) + t
        out.append([max(by_name, key=by_name.get), (e - s) * 1e-9])
    return sorted(out, key=lambda g: -g[1])


_COLLECTIVE_OPS = tuple(p + c for c in COLLECTIVES for p in ("%", ""))


def collective_ns(ops: list[tuple[float, float, str]], lo: float,
                  hi: float) -> float:
    """Nanoseconds of [lo, hi) in which a collective operation of ``ops``
    (a plane's ``XLA Ops`` events) ran: the union of their intervals,
    overlapping halves counted once."""
    return sum(e - s for s, e in _clip(merge(
        (s, e) for s, e, name in ops if name.startswith(_COLLECTIVE_OPS)),
        lo, hi))


def _short(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def _events(plane, line: str) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of the events of the plane's ``line``,
    sorted."""
    found = _line(plane, line)
    return sorted((e.start_ns, e.end_ns, e.name)
                  for e in (found.events if found is not None else ()))


def device_ops(plane) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, "module/op") of every leaf operation on the
    plane (``leaf_ops``)."""
    return leaf_ops(_events(plane, "XLA Ops"), _events(plane, "XLA Modules"))


def leaf_ops(ops: list[tuple[float, float, str]],
             mods: list[tuple[float, float, str]]
             ) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, "module/op") of every leaf operation of the
    sorted ``ops``, named by the event of the sorted ``mods`` (the
    compiled programs) that contains it: an operation that holds others
    (a loop's ``while``) is left out, so no time is counted twice."""
    out, j = [], 0
    for k, (s, e, name) in enumerate(ops):
        if k + 1 < len(ops) and ops[k + 1][0] < e:
            continue  # a container: the next operation starts inside it
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
        out.append((s, e, f"{mod}/{_short(name)}"))
    return out


def summarize(path: str, top: int = 10) -> dict:
    """``busy_s`` and ``collective_s`` (each a mean over the ``devices``
    device planes), ``window_s``, and the ``device_ops`` and
    ``idle_gaps`` lists of the breakdown, each ``[name, seconds]``,
    longest first (the idle gaps of the first device plane)."""
    pd = ProfileData.from_file(path)
    spans = host_spans(pd)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = windows[0]
    planes = device_planes(pd)
    if not planes:
        raise ValueError(f"no device plane with XLA Ops in {path}")
    busy, collective, by_op = [], [], {}
    gaps: list[tuple[float, float]] = []
    for i, plane in enumerate(planes):
        raw = _events(plane, "XLA Ops")
        collective.append(collective_ns(raw, lo, hi))
        ops = leaf_ops(raw, _events(plane, "XLA Modules"))
        union = _clip(merge((s, e) for s, e, _ in ops), lo, hi)
        busy.append(sum(e - s for s, e in union))
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
        if i == 0:
            edges = [lo] + [x for iv in union for x in iv] + [hi]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    n_planes = len(planes)
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n_planes * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": n_planes,
        "collective_s": sum(collective) / n_planes * 1e-9,
        "device_ops": [[n, t / n_planes * 1e-9] for n, t in ops_top],
        "idle_gaps": idle_gaps(gaps, spans, top),
    }
