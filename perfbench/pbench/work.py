"""The work a step needs, counted from the model's unpadded shapes.

These counts are the algorithm's, not the program's: a padded vocabulary,
zero-padded weight columns or a gathered ``max_seq`` view of the cache cost
the program time but add nothing here, so waste shows as a lower share of a
roofline, and removing it can raise the share but never past 100%.

Dense decoder with GQA attention and a SwiGLU FFN, tied embeddings, bf16
weights and K/V.  A multiply-add is two FLOPs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable

BF16 = 2


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @staticmethod
    def of(model: Dict) -> "Dims":
        d = model["hidden_size"]
        h = model["num_attention_heads"]
        return Dims(layers=model["num_hidden_layers"], d_model=d, heads=h,
                    kv_heads=model["num_key_value_heads"],
                    head_dim=model.get("head_dim") or d // h,
                    d_ff=model["intermediate_size"],
                    vocab=model["vocab_size"])

    # ---- parameters --------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the three FFN matrices of one layer."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d_model * (q + 2 * kv) + q * self.d_model \
            + 3 * self.d_model * self.d_ff

    @property
    def params(self) -> int:
        """Every parameter: layers (with their two norms), the tied
        embedding and the final norm."""
        return (self.layers * (self.layer_matmul_params + 2 * self.d_model)
                + self.vocab * self.d_model + self.d_model)

    @property
    def layer_weight_bytes(self) -> int:
        return BF16 * self.layers * (self.layer_matmul_params
                                     + 2 * self.d_model)

    @property
    def head_weight_bytes(self) -> int:
        """The tied embedding read whole by the LM head, and the final norm."""
        return BF16 * (self.vocab * self.d_model + self.d_model)

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * BF16

    # ---- FLOPs -------------------------------------------------------------
    @property
    def dense_flops_per_token(self) -> int:
        """The layers' matmuls for one token (no attention scores)."""
        return 2 * self.layers * self.layer_matmul_params

    @property
    def head_flops(self) -> int:
        return 2 * self.vocab * self.d_model

    def attn_flops(self, context: int) -> int:
        """Scores and weighted values of one query against ``context``
        keys, over every layer and head."""
        return 4 * self.layers * self.heads * self.head_dim * context


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def least_s(self, peak_flops: float, peak_bytes: float) -> float:
        """The least time the chip could take: the larger of the two
        roofs."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


def decode_work(dims: Dims, positions: Iterable[int]) -> Work:
    """One decode step of the slots at these positions (tokens already in
    each slot's cache): every weight once, each slot's K/V of its true
    length read and one row written, and the LM head per slot."""
    positions = list(positions)
    n = len(positions)
    flops = n * (dims.dense_flops_per_token + dims.head_flops) + sum(
        dims.attn_flops(p + 1) for p in positions)
    kv = sum(p + 1 for p in positions) * dims.kv_bytes_per_token
    return Work(flops, dims.layer_weight_bytes + dims.head_weight_bytes + kv)


def chunk_work(dims: Dims, off: int, length: int, final: bool) -> Work:
    """One prefill chunk of ``length`` true tokens at prompt offset ``off``:
    the layer weights once, the K/V of the ``off`` earlier tokens read and
    of the chunk written, and the LM head for the prompt's last token on
    its final chunk only."""
    flops = length * dims.dense_flops_per_token + sum(
        dims.attn_flops(off + j + 1) for j in range(length))
    nbytes = dims.layer_weight_bytes + (off + length) * dims.kv_bytes_per_token
    if final:
        flops += dims.head_flops
        nbytes += dims.head_weight_bytes
    else:
        nbytes += BF16 * length * dims.d_model      # the embedding rows read
    return Work(flops, nbytes)


def train_step_flops(dims: Dims, batch: int, seq: int) -> float:
    """Forward and backward FLOPs of one training step on ``batch`` rows
    of ``seq`` tokens, with no recompute: three times the forward's
    matmuls, causal attention over each token's own prefix."""
    tokens = batch * seq
    fwd = tokens * (dims.dense_flops_per_token + dims.head_flops) + batch * \
        sum(dims.attn_flops(j + 1) for j in range(seq))
    return 3.0 * fwd


def ag_gemm_work(m: int, k: int, n: int) -> Work:
    """One chip's AllGather-GEMM: ``m`` gathered rows of ``k`` times its
    ``k`` x ``n`` weight shard.  Each gathered row, the shard and the
    output are touched once in HBM."""
    return Work(2.0 * m * k * n, BF16 * (m * k + k * n + m * n))


def gemm_rs_work(m: int, k: int, n: int, chips: int) -> Work:
    """One chip's GEMM-ReduceScatter: its ``m`` x ``k`` rows times its
    ``k`` x ``n`` weight shard, reduced over the chips into ``m / chips``
    output rows.  Inputs read once, its output rows written once."""
    return Work(2.0 * m * k * n, BF16 * (m * k + k * n + m * n // chips))


def seam_calls(dims: Dims, batch: int, seq: int, chips: int
               ) -> Dict[str, list]:
    """The fused seam kernels one sequence-parallel training step runs on
    each chip, with their unpadded work.  Per layer, forward: the QKV and
    the gate/up projections gather the sequence (AllGather-GEMM), the
    output and down projections scatter it (GEMM-ReduceScatter); backward:
    each seam's input gradient runs the other kernel.  Once per step, the
    LM head gathers the final hidden states forward and scatters their
    gradient backward."""
    m, d, t = batch * seq, dims.d_model, chips
    qkv = (dims.heads + 2 * dims.kv_heads) * dims.head_dim // t
    o = dims.heads * dims.head_dim // t
    f = dims.d_ff // t
    v = -(-dims.vocab // t)
    ag = [ag_gemm_work(m, d, qkv), ag_gemm_work(m, d, 2 * f),
          ag_gemm_work(m, d, o), ag_gemm_work(m, d, f)] * dims.layers
    rs = [gemm_rs_work(m, o, d, t), gemm_rs_work(m, f, d, t),
          gemm_rs_work(m, qkv, d, t), gemm_rs_work(m, 2 * f, d, t)] \
        * dims.layers
    return {"ag_gemm": ag + [ag_gemm_work(m, d, v)],
            "gemm_rs": rs + [gemm_rs_work(m, v, d, t)]}
