"""Share of the traced window in which no operation ran on the device
(``1 - busy / window``, busy averaged over the chips), in a cg cell."""

from bench.metrics_util import idle_pct


def read(ctx):
    return idle_pct(ctx, "cg_iters")
