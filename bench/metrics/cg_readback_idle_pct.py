"""Device idle time with the host in ``cg.readback`` (the per-iteration
residual-norm sync) as a share of the traced window, in a cg cell."""

from bench import scopes


def read(ctx):
    if "cg_iters" not in ctx["samples"]:
        return None
    return scopes.idle_pct(scopes.for_run(ctx), "cg.readback")
