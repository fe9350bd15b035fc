"""95th percentile (linear interpolation) of the time to first token, from
submission, over every request whose first token came inside the window."""

import numpy as np


def read(ctx):
    ttft = ctx["samples"].get("ttft_s")
    return float(np.percentile(ttft, 95)) * 1e3 if ttft else None
