"""Tokens served in the window over the window."""


def read(ctx):
    s = ctx["samples"]
    return s["tokens"] / ctx["window_s"] if "serve_units" in s else None
