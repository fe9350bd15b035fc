"""The CG iterations' share of the chip's roofline: each iteration's least
time (``work.cg_iteration``: the assembled operator read once, 11 vector
passes) times the iterations, over the window."""

from bench.metrics_util import share_pct


def read(ctx):
    s = ctx["samples"]
    if "cg_iters" not in s:
        return None
    return share_pct(ctx, [s["iter_work"]], s["cg_iters"])
