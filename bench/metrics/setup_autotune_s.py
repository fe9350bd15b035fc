"""Seconds this process spent in autotune sweeps (the tuner's
``sweep_ns`` counter), nearly all of them in set-up; read in a traced run,
and nothing from a program that does not count them."""


def read(ctx):
    if ctx.get("trace") is None:
        return None
    from repro.kernels import tuning
    ns = tuning.stats().get("sweep_ns")
    return None if ns is None else ns / 1e9
