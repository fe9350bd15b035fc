"""Device time under the MoE exchange scopes (``moe.dispatch``,
``moe.combine``: the SF reduce into expert slots and the bcast back) as a
share of the traced window, in a serve cell."""

from bench import scopes


def read(ctx):
    if "serve_units" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx),
                            ("moe.dispatch", "moe.combine"))
