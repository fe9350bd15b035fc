"""Arithmetic shared by the metric readers in ``metrics/``."""

from __future__ import annotations

from typing import Dict, Iterable, Optional

from bench.work import Work, roofline_s

__all__ = ["idle_pct", "share_pct"]


def idle_pct(ctx: Dict, sample_key: str) -> Optional[float]:
    """``100 * (1 - busy / window)`` of the traced window, for cells whose
    samples carry ``sample_key``; nothing without a trace."""
    tr = ctx.get("trace")
    if tr is None or sample_key not in ctx["samples"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def share_pct(ctx: Dict, units: Iterable[Work], times: int = 1
              ) -> Optional[float]:
    """Summed roofline time of ``units`` (each repeated ``times``) over the
    window, in %; nothing on a device the peaks table lacks."""
    peaks = ctx.get("peaks")
    if peaks is None:
        return None
    least = sum(roofline_s(u, peaks)[0] for u in units) * times
    return 100.0 * least / ctx["window_s"]
