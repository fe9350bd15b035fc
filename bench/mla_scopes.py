"""Device time under the latent-attention scopes (``mla.q``, ``mla.kv``,
``mla.attn``), which ``scopes.py`` does not list among its program
prefixes: read from the inclusive time of each ``tf_op`` path component
that ``scopes.reduce_scopes`` keeps, and printed to stderr once a run."""

from __future__ import annotations

import sys
from typing import Dict, Optional

from bench import scopes

__all__ = ["mla_attn_s"]

_SHOWN = set()


def mla_attn_s(ctx: Dict) -> Optional[float]:
    """Seconds under ``mla.attn`` in a traced serve cell of a model with
    latent attention; None without a trace or without the scope."""
    if "mla_attn_units" not in ctx["samples"]:
        return None
    summary = scopes.for_run(ctx)
    if summary is None:
        return None
    comps = summary["components"]
    if id(summary) not in _SHOWN:
        _SHOWN.add(id(summary))
        w = summary["window_s"]
        for name in sorted(c for c in comps if c.startswith("mla.")):
            print(f"scopes: scope {name} {comps[name]:.6f} s "
                  f"({100 * comps[name] / w:.4f}% of the window)",
                  file=sys.stderr, flush=True)
    return comps.get("mla.attn")
