"""Device time under the SF combine scope (``sf.combine``: the segment
reduce of rows bound for one root) as a share of the traced window, in a
halo cell."""

from bench import scopes


def read(ctx):
    if "pairs" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx), "sf.combine")
