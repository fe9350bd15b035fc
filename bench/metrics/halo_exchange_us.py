"""Wall time of one halo pair (``global_to_local`` replace, then
``local_to_global`` sum, ending in ``block_until_ready``): the whole window
over the number of pairs in it."""


def read(ctx):
    pairs = ctx["samples"].get("pairs")
    return ctx["window_s"] / pairs * 1e6 if pairs else None
