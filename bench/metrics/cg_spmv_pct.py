"""Device time under the SpMV scopes (``mat.diag``, ``mat.offdiag``: the
ELL applies of the local and off-diagonal blocks) as a share of the traced
window, in a cg cell."""

from bench import scopes


def read(ctx):
    if "cg_iters" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx), ("mat.diag", "mat.offdiag"))
