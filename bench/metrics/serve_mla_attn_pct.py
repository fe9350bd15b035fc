"""Device time under the latent-attention scope (``mla.attn``: prefill's
K/V expansion and chunked attention, decode's absorbed attention over the
latent cache) as a share of the traced window, in a serve cell of a model
with latent attention; nothing where the program opens no such scope."""

from bench import scopes
from bench.mla_scopes import mla_attn_s


def read(ctx):
    t = mla_attn_s(ctx)
    return None if t is None else 100.0 * t / scopes.for_run(ctx)["window_s"]
