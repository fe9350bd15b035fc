"""Operations and bytes of the latent-attention MoE decoder's steps, from
shapes alone (the DeepSeek-V3 block of Kimi-K2; ``work.py`` has the rest).

As in ``work.py``, each count is the payload the algorithm must read and
write once: weights once a step, each latent cache row once, no tile,
padding or copy that an implementation adds.  The routed experts are
counted as the uniform routing that the seeded weights and token ids give:
a step of ``m`` picks reaches ``held * (1 - (1 - 1/experts)^m)`` of the
experts held here, and ``m * held / experts`` of its picks land here.
Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable

from bench.work import Work

__all__ = ["MLAShape", "shape_of", "weight_bytes", "decode_step", "prefill",
           "attn_decode", "attn_prefill"]


@dataclasses.dataclass(frozen=True)
class MLAShape:
    dense_layers: int
    moe_layers: int
    d_model: int
    n_heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    experts: int
    held: int
    topk: int
    expert_ff: int
    shared_ff: int
    dense_ff: int
    vocab: int
    weight_bytes: int = 2         # bf16 weights and latent cache
    router_bytes: int = 4         # f32 router and correction bias
    logit_bytes: int = 4          # f32 logits

    @property
    def layers(self) -> int:
        return self.dense_layers + self.moe_layers

    @property
    def mla_params(self) -> int:
        """Projections of one layer: W_qa, W_qb, W_kva, W_kvb, W_o."""
        D, H = self.d_model, self.n_heads
        return (D * self.q_rank + self.q_rank * H * (self.nope + self.rope)
                + D * (self.kv_rank + self.rope)
                + self.kv_rank * H * (self.nope + self.v_dim)
                + H * self.v_dim * D)

    @property
    def kvb_params(self) -> int:
        return self.kv_rank * self.n_heads * (self.nope + self.v_dim)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_ff

    @property
    def latent_row_bytes(self) -> int:
        """c_kv and k_rope of one token in one layer."""
        return (self.kv_rank + self.rope) * self.weight_bytes

    def experts_hit(self, picks: float) -> float:
        return self.held * (1.0 - (1.0 - 1.0 / self.experts) ** picks)


def shape_of(c: Dict) -> MLAShape:
    Ld = c["first_k_dense_replace"]
    return MLAShape(
        dense_layers=Ld, moe_layers=c["num_hidden_layers"] - Ld,
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v_dim=c["v_head_dim"], experts=c["n_routed_experts"],
        held=c["n_held_experts"], topk=c["num_experts_per_tok"],
        expert_ff=c["moe_intermediate_size"],
        shared_ff=c["moe_intermediate_size"] * c["n_shared_experts"],
        dense_ff=c["intermediate_size"], vocab=c["vocab_size"])


def weight_bytes(s: MLAShape, experts_hit: float) -> float:
    """Every weight a step reads but the routed experts, plus
    ``experts_hit`` held experts per MoE layer: attention and norms of
    every layer, the dense FFN, router, bias and shared expert of each MoE
    layer, the final norm and the output head (the embedding is read one
    row per token and counted with the tokens)."""
    D, wb = s.d_model, s.weight_bytes
    norms = (2 * D + s.q_rank + s.kv_rank) * wb
    attn = s.layers * (s.mla_params * wb + norms)
    dense = s.dense_layers * 3 * D * s.dense_ff * wb
    moe = s.moe_layers * ((D + 1) * s.experts * s.router_bytes
                          + 3 * D * s.shared_ff * wb
                          + experts_hit * s.expert_params * wb)
    return attn + dense + moe + D * wb + D * s.vocab * wb


def _token_flops(s: MLAShape, routed_per_token: float) -> float:
    """Per token, every layer's projections and FFN (attention over the
    context not included)."""
    D = s.d_model
    moe = 2 * (D * s.experts + 3 * D * s.shared_ff
               + routed_per_token * s.expert_params)
    return (2 * s.layers * s.mla_params + s.dense_layers * 6 * D * s.dense_ff
            + s.moe_layers * moe)


def attn_decode(s: MLAShape, contexts: Iterable[int]) -> Work:
    """The absorbed attention of one decode step over each sequence's
    latent cache (``contexts``: attended lengths): per layer, the query and
    output absorptions through W_kvb (read once) and ``2 H (kv_rank + rope
    + kv_rank)`` operations per attended position; every attended latent
    row read once."""
    ctx = list(contexts)
    if not ctx:
        return Work(0.0, 0.0)
    H, B, L = s.n_heads, len(ctx), s.layers
    per_pos = 2 * H * (2 * s.kv_rank + s.rope)
    flops = L * (per_pos * sum(ctx) + 2 * B * s.kvb_params)
    byts = L * (s.latent_row_bytes * sum(ctx) + s.kvb_params * s.weight_bytes)
    return Work(float(flops), float(byts))


def attn_prefill(s: MLAShape, n: int) -> Work:
    """The expanded causal attention of a prompt of ``n`` tokens: per
    layer, the K/V expansion ``c_kv W_kvb`` and ``H (nope + rope + v)
    n (n + 1)`` operations of scores and weighted values; the latent rows,
    W_kvb and the queries read once, the outputs written once."""
    H, L, wb = s.n_heads, s.layers, s.weight_bytes
    flops = L * (H * (s.nope + s.rope + s.v_dim) * n * (n + 1)
                 + 2 * n * s.kvb_params)
    byts = L * wb * (n * (s.kv_rank + s.rope) + s.kvb_params
                     + n * H * (s.nope + s.rope) + n * H * s.v_dim)
    return Work(float(flops), float(byts))


def decode_step(s: MLAShape, contexts: Iterable[int]) -> Work:
    """One decode step of the active sequences (``contexts``: each one's
    attended length, cached tokens plus the new one): every weight but the
    routed experts once, the held experts the step's ``B * topk`` picks
    reach, each attended latent row once, one new latent row, one
    embedding row and one row of logits per sequence."""
    ctx = list(contexts)
    B = len(ctx)
    if not B:
        return Work(0.0, 0.0)
    routed = s.topk * s.held / s.experts          # picks landing here
    attn = attn_decode(s, ctx)
    flops = B * _token_flops(s, routed) + attn.flops \
        - 2 * B * s.layers * s.kvb_params          # counted in mla_params
    byts = weight_bytes(s, s.experts_hit(B * s.topk)) \
        + s.layers * s.latent_row_bytes * sum(ctx) \
        + B * (s.d_model * s.weight_bytes + s.vocab * s.logit_bytes)
    return Work(float(flops + 2 * B * s.d_model * s.vocab), float(byts))


def prefill(s: MLAShape, n: int) -> Work:
    """Prefill of one prompt of ``n`` real tokens (the bucket's pad tail is
    not counted): the causal attention, the FFNs of every token, the output
    head at the last position only; every weight once (the held experts
    the prompt's picks reach), the embedding rows, the latent rows written
    and one row of logits."""
    routed = s.topk * s.held / s.experts
    attn = attn_prefill(s, n)
    flops = n * _token_flops(s, routed) + attn.flops \
        - 2 * n * s.layers * s.kvb_params + 2 * s.d_model * s.vocab
    byts = weight_bytes(s, s.experts_hit(n * s.topk)) \
        + n * s.d_model * s.weight_bytes \
        + s.layers * n * s.latent_row_bytes + s.vocab * s.logit_bytes
    return Work(float(flops), float(byts))
