"""The work a serving step of a DeepSeek-V2 decoder needs (latent
attention, routed and shared experts, an untied head), counted from the
model's unpadded shapes, with the interface of ``pbench/work.py``.

FLOPs are twice the parameters a token uses: the attention projections
(query, latent down-projection, output), the dense layer's FFN, each
expert layer's router, shared experts and ``top-k`` routed experts, and
the LM head where it runs.  The latent up-projection belongs to the
attention, which is counted at true lengths in whichever form is cheaper
for the call: absorbed (the up-projection folded into each query and each
output, scores against the cached latent) or not (keys and values expanded
from the latent of every context token, scores per head).  So no later
change of form can push a share past 100%.

Bytes: a decode step reads every weight outside the routed experts once,
the routed experts that its ``t`` rows are expected to touch under uniform
routing (``E (1 - (1 - k / E)^t)`` of each layer's ``E``), and each slot's
cached latent (``kv_lora_rank + qk_rope_head_dim`` bf16 values a token and
a layer) of its true length once.  A prefill chunk reads every weight and
the latent of the earlier tokens, and writes its own.  The router is
float32.  (No ``from __future__ import annotations`` here: the harness
loads this file by path, outside ``sys.modules``, where a dataclass cannot
resolve string annotations.)
"""
from dataclasses import dataclass
from typing import Dict, Iterable

from pbench.work import BF16, Work

F32 = 4


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    expert_ff: int
    experts: int
    top_k: int
    shared: int
    vocab: int

    @staticmethod
    def of(model: Dict) -> "Dims":
        return Dims(layers=model["num_hidden_layers"],
                    dense_layers=model.get("first_k_dense_replace", 0),
                    d_model=model["hidden_size"],
                    heads=model["num_attention_heads"],
                    kv_rank=model["kv_lora_rank"],
                    nope=model["qk_nope_head_dim"],
                    rope=model["qk_rope_head_dim"],
                    v_dim=model["v_head_dim"],
                    d_ff=model["intermediate_size"],
                    expert_ff=model["moe_intermediate_size"],
                    experts=model["n_routed_experts"],
                    top_k=model["num_experts_per_tok"],
                    shared=model.get("n_shared_experts") or 0,
                    vocab=model["vocab_size"])

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    # ---- parameters --------------------------------------------------------
    @property
    def attn_proj_params(self) -> int:
        """Query, latent down-projection and output of one layer."""
        d, h = self.d_model, self.heads
        return (d * h * (self.nope + self.rope)
                + d * (self.kv_rank + self.rope) + h * self.v_dim * d)

    @property
    def up_params(self) -> int:
        """The latent up-projection of one layer (keys' and values' parts)."""
        return self.kv_rank * self.heads * (self.nope + self.v_dim)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_ff

    @property
    def router_params(self) -> int:
        return self.d_model * self.experts

    @property
    def per_token_params(self) -> int:
        """The matmul parameters one token uses outside the attention's
        up-projection and scores, over every layer."""
        d = self.d_model
        return (self.layers * self.attn_proj_params
                + self.dense_layers * 3 * d * self.d_ff
                + self.expert_layers * (self.router_params
                                        + (self.shared + self.top_k)
                                        * self.expert_params))

    @property
    def latent_bytes_per_token(self) -> int:
        return self.layers * (self.kv_rank + self.rope) * BF16

    def weight_bytes(self, experts_read: float) -> float:
        """Every layer weight and norm, with ``experts_read`` routed experts
        of each expert layer."""
        d = self.d_model
        norms = self.layers * (2 * d + self.kv_rank)
        return (BF16 * (self.layers * (self.attn_proj_params
                                       + self.up_params)
                        + self.dense_layers * 3 * d * self.d_ff
                        + self.expert_layers * (self.shared + experts_read)
                        * self.expert_params + norms)
                + F32 * self.expert_layers * self.router_params)

    @property
    def head_weight_bytes(self) -> int:
        return BF16 * (self.vocab * self.d_model + self.d_model)

    @property
    def head_flops(self) -> int:
        return 2 * self.vocab * self.d_model

    # ---- attention ---------------------------------------------------------
    def attn_flops(self, contexts) -> float:
        """One layer's attention for queries that see ``contexts`` keys
        each (the last the call's whole context), in the cheaper form."""
        contexts = list(contexts)
        h, r = self.heads, self.kv_rank
        total = sum(contexts)
        absorbed = len(contexts) * 2 * h * r * (self.nope + self.v_dim) \
            + 2 * h * (2 * r + self.rope) * total
        expanded = 2 * self.up_params * max(contexts) \
            + 2 * h * (self.nope + self.rope + self.v_dim) * total
        return min(absorbed, expanded)


def _experts_touched(dims: Dims, rows: int) -> float:
    e, k = dims.experts, dims.top_k
    return e * (1.0 - (1.0 - k / e) ** rows)


def decode_work(dims: Dims, positions: Iterable[int]) -> Work:
    """One decode step of the slots at these positions (tokens already in
    each slot's cache)."""
    positions = list(positions)
    n = len(positions)
    attn = sum(dims.attn_flops([p + 1]) for p in positions)
    flops = n * (2 * dims.per_token_params + dims.head_flops) \
        + dims.layers * attn
    latent = sum(p + 1 for p in positions) * dims.latent_bytes_per_token
    return Work(flops, dims.weight_bytes(_experts_touched(dims, n))
                + dims.head_weight_bytes + latent)


def chunk_work(dims: Dims, off: int, length: int, final: bool) -> Work:
    """One prefill chunk of ``length`` true tokens at prompt offset
    ``off``, with the LM head on the prompt's last token of its final
    chunk only."""
    flops = length * 2 * dims.per_token_params + dims.layers * \
        dims.attn_flops(off + j + 1 for j in range(length))
    nbytes = dims.weight_bytes(dims.experts) \
        + (off + length) * dims.latent_bytes_per_token
    if final:
        flops += dims.head_flops
        nbytes += dims.head_weight_bytes
    else:
        nbytes += BF16 * length * dims.d_model      # the embedding rows read
    return Work(flops, nbytes)
