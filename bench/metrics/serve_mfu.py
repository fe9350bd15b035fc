"""The served model's share of the chip's roofline: the least time of every
prefill and decode step run in the window (``work.lm_prefill``,
``work.lm_decode_step``), summed, over the window."""

from bench.metrics_util import share_pct


def read(ctx):
    units = ctx["samples"].get("serve_units")
    return share_pct(ctx, units) if units else None
