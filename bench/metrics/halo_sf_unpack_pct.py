"""Device time under the SF unpack scope (``sf.unpack``: exchanged rows
scattered into the destination, replace or add) as a share of the traced
window, in a halo cell."""

from bench import scopes


def read(ctx):
    if "pairs" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx), "sf.unpack")
