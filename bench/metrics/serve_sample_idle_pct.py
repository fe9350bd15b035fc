"""Device idle time with the host in ``serve.sample`` (reading the sampled
tokens back) as a share of the traced window, in a serve cell."""

from bench import scopes


def read(ctx):
    if "serve_units" not in ctx["samples"]:
        return None
    return scopes.idle_pct(scopes.for_run(ctx), "serve.sample")
