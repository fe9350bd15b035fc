"""Operations and bytes that one unit of work needs, from shapes alone.

Each count is the payload the algorithm must read and write once: no tile,
padding, lane or copy that an implementation adds, so a kernel that stops
amplifying its reads shows a higher share and no share can pass 100%.
Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Sequence, Tuple

__all__ = ["Work", "roofline_s", "stencil_nnz", "star_connected_leaves",
           "halo_pair", "cg_iteration", "LMShape", "lm_weight_bytes",
           "lm_decode_step", "lm_prefill"]


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def roofline_s(work: Work, peaks: Dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flops = work.flops / peaks["bf16_flops_per_s"]
    t_bytes = work.bytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


# ------------------------------------------------------------ structured grid
def stencil_nnz(shape: Sequence[int]) -> int:
    """Nonzeros of the star (2d+1)-point operator on a non-periodic grid
    with off-domain neighbours dropped: one diagonal per cell plus two
    entries per pair of face-adjacent cells."""
    n = 1
    for e in shape:
        n *= e
    pairs = sum(n // e * (e - 1) for e in shape)
    return n + 2 * pairs


def star_connected_leaves(shape: Sequence[int], splits: Sequence[Sequence[int]]
                          ) -> int:
    """Local (ghosted) positions that a width-1 star-stencil DMDA connects:
    every owned cell once, plus one ghost copy of each cell per subdomain
    face it lies on (corner ghosts of a star stencil stay holes)."""
    n = 1
    for e in shape:
        n *= e
    ghosts = 0
    for d, cuts in enumerate(splits):
        inner = len(cuts) - 2                 # interior cuts along dim d
        ghosts += 2 * inner * (n // shape[d])  # both sides of each cut
    return n + ghosts


def halo_pair(n_global: int, n_leaves: int, dof: int,
              itemsize: int = 4) -> Work:
    """One DMGlobalToLocal (replace) plus one DMLocalToGlobal (sum):
    global_to_local reads each global value once and writes each connected
    leaf once; local_to_global reads each leaf once and writes each global
    value once, with one add per leaf beyond the first of its root."""
    row = dof * itemsize
    return Work(flops=float((n_leaves - n_global) * dof),
                bytes=float(2 * (n_global + n_leaves) * row))


def cg_iteration(nnz: int, n: int, itemsize: int = 4,
                 index_bytes: int = 4) -> Work:
    """One unpreconditioned CG iteration on an assembled sparse matrix.

    The operator is read once: a value and a column index per nonzero (what
    an assembled CSR/ELL matrix is, and what HPCG's rules require; a
    matrix-free stencil would be another configuration).  Vectors move in
    the three passes the iteration's dependencies force: Ap = A p with
    <p, Ap> (read p, write Ap); x += a p, r -= a Ap with <r, r> (read x, p,
    r, Ap; write x, r); p = r + b p (read r, p; write p) -- 11 vectors.
    Operations: 2 per nonzero, 2 dots and 3 axpys."""
    return Work(flops=float(2 * nnz + 10 * n),
                bytes=float(nnz * (itemsize + index_bytes) + 11 * n * itemsize))


# ------------------------------------------------------------------ MoE LM
@dataclasses.dataclass(frozen=True)
class LMShape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    experts: int
    topk: int
    expert_ff: int
    vocab: int
    weight_bytes: int = 2         # bf16 weights and KV cache
    router_bytes: int = 4         # f32 router
    logit_bytes: int = 4          # f32 logits

    @property
    def attn_params(self) -> int:
        D, H, K, hd = self.d_model, self.n_heads, self.n_kv_heads, \
            self.head_dim
        return 2 * D * H * hd + 2 * D * K * hd

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_ff

    @property
    def kv_row_bytes(self) -> int:
        """K and V of one token in one layer."""
        return 2 * self.n_kv_heads * self.head_dim * self.weight_bytes


def lm_weight_bytes(s: LMShape) -> int:
    """Every weight a step reads: all layers (attention, router, all
    experts, norms), the final norm and the output head.  The embedding
    table is read one row per token and counted with the tokens."""
    wb = s.weight_bytes
    layer = (s.attn_params + s.experts * s.expert_params) * wb \
        + s.d_model * s.experts * s.router_bytes + 2 * s.d_model * wb
    return s.n_layers * layer + s.d_model * wb + s.d_model * s.vocab * wb


def _token_linear_flops(s: LMShape) -> int:
    """Per token per layer: projections, router, top-k experts."""
    return 2 * (s.attn_params + s.d_model * s.experts
                + s.topk * s.expert_params)


def lm_decode_step(s: LMShape, contexts: Iterable[int]) -> Work:
    """One decode step of the active sequences; ``contexts`` holds each
    one's attended length (cached tokens plus the new one).

    Bytes: every weight once (all experts: a step of 32 tokens x top-2 hits
    each of 16 experts unless routing skews), each sequence's cached K/V
    once, one new K/V row, one embedding row and one row of logits per
    sequence.  Inactive batch slots are padding and count nothing."""
    ctx = list(contexts)
    B, L = len(ctx), s.n_layers
    if not B:
        return Work(0.0, 0.0)
    attn = sum(4 * s.n_heads * s.head_dim * c for c in ctx)
    flops = L * (B * _token_linear_flops(s) + attn) \
        + B * 2 * s.d_model * s.vocab
    kv = L * s.kv_row_bytes * (sum(ctx) - B)       # read the cached rows
    kv += L * s.kv_row_bytes * B                   # write the new rows
    rows = B * s.d_model * s.weight_bytes + B * s.vocab * s.logit_bytes
    return Work(float(flops), float(lm_weight_bytes(s) + kv + rows))


def lm_prefill(s: LMShape, n: int) -> Work:
    """Prefill of one prompt of ``n`` real tokens (the bucket's pad tail is
    not counted): causal attention over the prompt, top-k experts per token,
    the output head at the last position only.  Bytes: every weight once,
    the embedding rows, the K/V rows written, one row of logits."""
    L = s.n_layers
    attn = 4 * s.n_heads * s.head_dim * n * (n + 1) // 2
    flops = L * (n * _token_linear_flops(s) + attn) + 2 * s.d_model * s.vocab
    byts = lm_weight_bytes(s) + n * s.d_model * s.weight_bytes \
        + L * n * s.kv_row_bytes + s.vocab * s.logit_bytes
    return Work(float(flops), float(byts))
