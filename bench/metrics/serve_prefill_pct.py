"""Device time under the prefill program's scope (``serve.prefill``) as a
share of the traced window, in a serve cell."""

from bench import scopes


def read(ctx):
    if "serve_units" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx), "serve.prefill")
