"""Wall time per CG iteration: the whole window over all iterations of the
sets run in it (the set in progress at the close is finished and counted)."""


def read(ctx):
    iters = ctx["samples"].get("cg_iters")
    return ctx["window_s"] / iters * 1e3 if iters else None
