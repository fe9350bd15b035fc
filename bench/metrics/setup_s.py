"""Set-up time: from the start of ``run.py`` to the window's start
(loading, building the problem, warming every shape, compiling)."""


def read(ctx):
    return ctx["setup_s"]
