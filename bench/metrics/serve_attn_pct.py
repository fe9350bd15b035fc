"""Device time under the attention scope (``model.attn``, prefill and
decode, the K/V repeat included) as a share of the traced window, in a
serve cell."""

from bench import scopes


def read(ctx):
    if "serve_units" not in ctx["samples"]:
        return None
    return scopes.scope_pct(scopes.for_run(ctx), "model.attn")
