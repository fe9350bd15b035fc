"""Reduce a profiler trace (``.xplane.pb``) to device busy time and a
breakdown.

The traced window is the host span ``bench.window`` that ``run.py`` opens
around the measured loop.  Inside it:

* busy: the union of the intervals in which an operation ran on a device
  (the device plane's ``XLA Ops`` line), averaged over the devices;
* device ops: the operations that took most device time, each by its self
  time (a ``while`` or ``call`` op's duration less the ops nested in it);
* idle gaps: the device's idle intervals, each named by the innermost
  benchmark span (``halo.*``, ``cg.*``, ``serve.*``) the host was in at
  the gap's middle, summed by name.

Only JAX's own ``ProfileData`` reader is used.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

__all__ = ["find_xplane", "reduce_trace", "union_length", "gaps"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("halo.", "cg.", "serve.")
DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
TOP = 10
NAME_CHARS = 160      # an XLA op's name is its whole HLO line; keep the head


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _merged(iv: np.ndarray) -> np.ndarray:
    """Sorted, non-overlapping union of (n, 2) [start, end) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def union_length(iv: np.ndarray) -> float:
    m = _merged(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
    return float(np.sum(m[:, 1] - m[:, 0])) if m.size else 0.0


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Idle intervals of [lo, hi) not covered by ``iv``."""
    m = _merged(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
    edges, cur = [], lo
    for s, e in m:
        if s > cur:
            edges.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        edges.append((cur, hi))
    return np.asarray([g for g in edges if g[1] > g[0]],
                      dtype=np.float64).reshape(-1, 2)


def _events(plane) -> List[Tuple[str, str, float, float]]:
    return [(line.name, ev.name, float(ev.start_ns), float(ev.end_ns))
            for line in plane.lines for ev in line.events]


def _self_times(evs: Sequence[Tuple[str, float, float]]
                ) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self time) of properly nested events: each
    event's duration less that of the events directly inside it."""
    out, stack = [], []                 # stack of indices into out
    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            out[stack[-1]] = parent[:3] + (parent[3] - (min(e, parent[2]) - s),)
        out.append((name, s, e, e - s))
        stack.append(len(out) - 1)
    return out


class _SpanIndex:
    """Innermost benchmark span at a time: spans are properly nested, so it
    is the latest-started span that still covers the time."""

    def __init__(self, spans: Sequence[Tuple[float, float, str]]):
        spans = sorted(spans)
        self.start = np.asarray([s for s, _, _ in spans], dtype=np.float64)
        self.end = np.asarray([e for _, e, _ in spans], dtype=np.float64)
        self.name = [n for _, _, n in spans]
        self.reach = np.maximum.accumulate(self.end) if spans else self.end

    def at(self, t: float) -> str:
        i = int(np.searchsorted(self.start, t, side="right")) - 1
        while i >= 0 and self.reach[i] > t:
            if self.end[i] > t:
                return self.name[i]
            i -= 1
        return "outside any span"


def reduce_trace(path: str, *, window_span: str = WINDOW_SPAN,
                 span_prefixes: Sequence[str] = SPAN_PREFIXES,
                 top: int = TOP) -> Dict:
    """-> {"busy_s", "window_s", "devices", "device_ops", "idle_gaps"}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host.extend(_events(plane))
        elif plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
    win = [(s, e) for _, name, s, e in host if name == window_span]
    if not win:
        raise ValueError(f"trace has no {window_span!r} span")
    lo, hi = win[0]
    spans = _SpanIndex([(s, e, name) for _, name, s, e in host
                        if name.startswith(tuple(span_prefixes))])

    busy, op_time, idle = [], {}, {}
    for i, plane in enumerate(devices):
        evs = [(n, s, e) for line, n, s, e in _events(plane) if line == OP_LINE]
        iv = np.asarray([(max(s, lo), min(e, hi)) for _, s, e in evs
                         if e > lo and s < hi], dtype=np.float64).reshape(-1, 2)
        busy.append(union_length(iv))
        for name, s, e, own in _self_times(evs):
            d = own * max(0.0, min(e, hi) - max(s, lo)) / max(e - s, 1e-30)
            if d > 0:
                name = name[:NAME_CHARS]
                op_time[name] = op_time.get(name, 0.0) + d
        if i == 0:
            for g0, g1 in gaps(iv, lo, hi):
                key = spans.at(0.5 * (g0 + g1))
                idle[key] = idle.get(key, 0.0) + (g1 - g0)
    if not devices:
        raise ValueError("trace has no device plane")
    nd = len(devices)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": float(np.mean(busy)) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": nd,
        "device_ops": [[n, v * 1e-9 / nd] for n, v in rank(op_time)],
        "idle_gaps": [[n, v * 1e-9] for n, v in rank(idle)],
    }
