"""The halo pairs' share of the chip's roofline: each pair's least time
(the larger of its operations over peak FLOP/s and its payload bytes over
peak bandwidth, ``work.halo_pair``) times the pairs, over the window."""

from bench.metrics_util import share_pct


def read(ctx):
    s = ctx["samples"]
    if "pairs" not in s:
        return None
    return share_pct(ctx, [s["pair_work"]], s["pairs"])
