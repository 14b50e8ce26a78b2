"""From a profiler trace (``.xplane.pb``) to the device's busy time, its
idle gaps and the operations that took longest.

Only ``jax.profiler.ProfileData`` is used.  A device plane is one named
``/device:<PLATFORM>:<n>``; its operations are the events of its
``XLA Ops`` line, each placed in the ``XLA Modules`` event (the compiled
program) that contains it.  Busy time is the union of the operation
intervals.  The window is the host span ``bench.window``, which the
harness opens at the first timed submission and closes at the end.  Each
idle gap inside it is named after the ``bench.*`` host span that overlaps
it most.  Host and device clocks in a trace agree to about a millisecond
(the recorded fixture in ``tests/data`` shows the device 1.1 ms behind),
which is small beside the window and the gaps that matter.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, Optional

from jax.profiler import ProfileData

WINDOW = "bench.window"
HOST_PREFIX = "bench."


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
    spans = []
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return spans


def _short(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def device_ops(plane) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, "module/op") of every leaf operation on the
    plane: an operation that holds others (a loop's ``while``) is left
    out, so no time is counted twice."""
    mod_line = _line(plane, "XLA Modules")
    mods = sorted((e.start_ns, e.end_ns, e.name)
                  for e in (mod_line.events if mod_line else ()))
    ops = sorted((e.start_ns, e.end_ns, e.name)
                 for e in _line(plane, "XLA Ops").events)
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
    """``busy_s`` (mean over the device planes), ``window_s``, and the
    ``device_ops`` and ``idle_gaps`` lists of the breakdown, each
    ``[name, seconds]``, longest first."""
    pd = ProfileData.from_file(path)
    spans = host_spans(pd)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = windows[0]
    planes = device_planes(pd)
    if not planes:
        raise ValueError(f"no device plane with XLA Ops in {path}")
    busy, by_op = [], {}
    gaps: list[tuple[float, float]] = []
    for i, plane in enumerate(planes):
        ops = device_ops(plane)
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
    others = [(s, e, n) for s, e, n in spans if n != WINDOW]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named = [(e - s, _blame(s, e, others)) for s, e in longest]
    n_planes = len(planes)
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n_planes * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, t / n_planes * 1e-9] for n, t in ops_top],
        "idle_gaps": [[n, t * 1e-9] for t, n in named],
    }


def _blame(s: float, e: float,
           spans: list[tuple[float, float, str]]) -> str:
    best: Optional[str] = None
    most = 0.0
    for a, b, name in spans:
        ov = min(b, e) - max(a, s)
        if ov > most:
            best, most = name, ov
    return best or "no host span"
