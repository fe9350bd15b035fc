"""The latent-attention kernel's share of its roofline: the least time of
every prefill's and decode step's latent-attention work in the window
(``work_mla.attn_prefill``, ``work_mla.attn_decode``), summed, over the
device time under the ``mla.attn`` scope."""

from bench.mla_scopes import mla_attn_s
from bench.work import roofline_s


def read(ctx):
    t = mla_attn_s(ctx)
    units = ctx["samples"].get("mla_attn_units")
    if t is None or not units or ctx.get("peaks") is None:
        return None
    return 100.0 * sum(roofline_s(u, ctx["peaks"])[0] for u in units) / t
