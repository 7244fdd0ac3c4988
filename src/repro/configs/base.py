"""Config system: model / parallelism / run configs for the whole framework.

Every assigned architecture gets a module in ``repro/configs/<id>.py`` exposing
``CONFIG: ModelConfig``.  Shapes (train_4k / prefill_32k / decode_32k / long_500k)
are defined here once and attached per-arch via ``input_specs``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Layer-pattern vocabulary.  A model is a sequence of blocks; homogeneous runs
# are scanned (keeps HLO small), heterogeneous periods are python-unrolled
# inside a scanned "period".
# ---------------------------------------------------------------------------
ATTN = "attn"          # softmax attention (GQA)
MLA = "mla"            # DeepSeek multi-head latent attention
MAMBA = "mamba"        # Mamba-1 selective-scan mixer
RWKV = "rwkv6"         # RWKV-6 (Finch) time-mix
DENSE_FFN = "ffn"      # SwiGLU / GeGLU dense FFN
MOE_FFN = "moe"        # routed expert FFN


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_ffn: int                  # d_ff of each routed expert
    num_shared_experts: int = 0      # DeepSeek-style shared expert(s)
    shared_ffn: int = 0              # d_ff of the shared expert path
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    norm_topk: bool = True           # renormalise the top-k gates to sum 1


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                 # 0 -> ceil(d_model/16)


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64             # LoRA rank of the data-dependent decay
    token_shift: bool = True


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: Optional[int] = 1536    # None: one uncompressed w_q
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int                   # query heads (0 for attention-free archs)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    # Per-layer pattern: list of (mixer, ffn) tuples describing ONE period,
    # repeated num_layers/len(pattern) times.  Default: [(ATTN, DENSE_FFN)].
    pattern: Tuple[Tuple[str, str], ...] = ((ATTN, DENSE_FFN),)
    # How many leading layers override the pattern (DeepSeek: 3 dense first).
    leading_dense_layers: int = 0
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    mla: Optional[MLAConfig] = None
    # attention details
    rope_theta: float = 10000.0
    rope_style: str = "rope"         # rope | mrope | none
    rope_scaling: Optional[YarnConfig] = None
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # modality frontend stub: None | "audio_frames" | "vision_patches"
    frontend: Optional[str] = None
    num_codebooks: int = 1           # musicgen EnCodec codebooks
    # training-time specifics
    mtp_depth: int = 0               # DeepSeek multi-token-prediction heads
    max_seq_len: int = 524288
    sub_quadratic: bool = False      # True -> long_500k cell is runnable
    compute_dtype: str = "bfloat16"  # activation dtype (fp32 for num. tests)

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        from repro.models.model import count_params_analytic
        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params_analytic
        return count_params_analytic(self, active_only=True)


# ---------------------------------------------------------------------------
# Parallelism / run configuration
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ParallelConfig:
    """How a model maps onto the device mesh.

    mesh axes are ("pod", "ep", "data", "model"); single-pod meshes drop
    "pod" and ep == 0 (the default) drops "ep".
    - dp axes: ("pod", "ep", "data") -> batch (a dedicated EP axis still
      carries batch outside the MoE seam — tokens are sharded over it and
      the expert exchange is what crosses it)
    - tp/sp axis: "model"      -> Megatron TP with sequence sharding
    - ep: EITHER a dedicated "ep" mesh axis (``ep > 0``: experts sharded
      over it, first-class factor of total_devices) OR implied — experts
      over "model" by default, over ("data","model") jointly when
      ``ep_over_dp`` (DeepSeek-scale expert counts)
    """
    tp: int = 1
    dp: int = 1
    pods: int = 1
    ep: int = 0                      # dedicated EP axis size (0 -> no axis;
    #                                  EP implied by ep_over_dp / "model")
    ep_over_dp: bool = False         # experts sharded over (data, model) jointly
    zero3: bool = False              # FSDP-style param gather per layer
    pp: int = 1                      # pipeline stages (reinterprets pod axis)
    remat: str = "none"              # none | selective | full
    overlap_mode: str = "decomposed" # default seam mode (overlap.VALID_MODES)
    wire_dtype: Optional[str] = None # forward-wire precision for TP seams
    #                                  (None | int8 | fp8_e4m3 | int4);
    #                                  lossy — cotangents never quantized
    max_logit_rmse: Optional[float] = None  # error budget gating the
    #                                  autotuner's wire_dtype sweep
    comm_chunks: int = 0             # 0 -> auto (=tp); medium-grained chunking
    plan_profile: Optional[str] = None  # tuned per-seam profile JSON
    #                                  (repro.tuning; stale files are ignored)
    scatter_axis: str = "auto"       # residual-stream activation layout:
    #                                  "auto" (profile/default), "seq"
    #                                  (Megatron-SP) or "hidden" (replicated)
    grad_compress: bool = False      # int8 cross-pod gradient all-reduce
    seq_shard_attn: bool = False     # shard sequence (ring attn) when heads don't divide
    fuse_w13: bool = False           # fuse parallel input projections (w1|w3,
    #                                  mamba x|z) into ONE AllGather-GEMM seam
    kernel_decode: bool = False      # fused Pallas MLA-decode attention

    @property
    def total_devices(self) -> int:
        return self.tp * self.dp * self.pods * max(self.ep, 1)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> bool:
    """long_500k only runs for sub-quadratic archs (SSM / hybrid)."""
    if shape.name == "long_500k" and not model.sub_quadratic:
        return False
    return True


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
ARCH_IDS: List[str] = [
    "jamba_v01_52b",
    "llama4_scout_17b_a16e",
    "deepseek_v3_671b",
    "codeqwen15_7b",
    "phi4_mini_38b",
    "qwen15_110b",
    "minicpm_2b",
    "musicgen_medium",
    "qwen2_vl_72b",
    "rwkv6_3b",
    "deepseek_v2_lite",
]

# the paper's own eval model (GPT-3 175B GEMM shapes come from this config)
PAPER_ARCH_IDS: List[str] = ["gpt3_175b"]


def get_config(arch: str) -> ModelConfig:
    import importlib

    arch = arch.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    import importlib

    arch = arch.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    if hasattr(mod, "SMOKE_CONFIG"):
        return mod.SMOKE_CONFIG
    return shrink(mod.CONFIG)


def shrink(cfg: ModelConfig, **overrides: Any) -> ModelConfig:
    """Generic reduction used for smoke testing: tiny dims, same family/pattern."""
    period = len(cfg.pattern)
    small: Dict[str, Any] = dict(
        num_layers=max(2 * period, 2),
        d_model=128,
        num_heads=4 if cfg.num_heads else 0,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads else 0,
        d_ff=256,
        vocab_size=512,
        head_dim=32 if cfg.num_heads else 0,
        leading_dense_layers=min(cfg.leading_dense_layers, 1),
        max_seq_len=4096,
    )
    if cfg.moe is not None:
        small["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2), expert_ffn=128,
            shared_ffn=128 if cfg.moe.shared_ffn else 0)
    if cfg.mla is not None:
        q_lora = None if cfg.mla.q_lora_rank is None else 64
        small["mla"] = MLAConfig(q_lora_rank=q_lora, kv_lora_rank=32,
                                 qk_nope_head_dim=32, qk_rope_head_dim=16,
                                 v_head_dim=32)
    if cfg.rwkv is not None:
        small["rwkv"] = RWKVConfig(head_dim=32, decay_lora=16)
    if cfg.mamba is not None:
        small["mamba"] = MambaConfig(d_state=8, d_conv=4, expand=2)
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
