"""Device time under any SF scope (``sf.*``: the SpMV's ghost exchange) as
a share of the traced window, in a cg cell."""

from bench import scopes


def read(ctx):
    if "cg_iters" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx),
                            lambda name: name.startswith("sf."))
