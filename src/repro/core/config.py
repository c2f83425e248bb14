"""Configuration dataclasses for the DPUV4E-on-TPU framework.

Three config families:
  * ArchConfig   -- an LM-family architecture (the assigned arch pool).
  * CNNConfig    -- a CNN from the paper's own evaluation zoo (Table III/IV).
  * EngineConfig -- the DPUV4E engine feature set (the paper's technique),
                    threaded through every model.
  * ShapeConfig  -- an assigned (seq_len, global_batch, kind) input shape.
  * TrainConfig  -- optimizer / schedule / fault-tolerance knobs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# LM architectures
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # --- attention variants -------------------------------------------------
    qkv_bias: bool = False
    # Per-layer block pattern, cycled: entries in
    # {"global", "local", "recurrent", "mamba"}.
    block_pattern: Tuple[str, ...] = ("global",)
    local_window: int = 4096
    attn_softcap: float = 0.0        # gemma2 attention-logit softcap (0 = off)
    final_softcap: float = 0.0       # gemma2 final-logit softcap (0 = off)
    rope_theta: float = 10000.0
    mrope: bool = False              # qwen2-vl multimodal RoPE
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)

    # --- MLP ----------------------------------------------------------------
    mlp_act: str = "silu"            # silu -> SwiGLU, gelu -> GeGLU
    mlp_gated: bool = True           # False: plain up/act/down (nemotron)
    tie_embeddings: bool = True

    # --- MoE ----------------------------------------------------------------
    n_experts: int = 0
    topk: int = 0
    capacity_factor: float = 1.25

    # --- SSM / hybrid -------------------------------------------------------
    ssm_state: int = 0               # mamba1 d_state
    ssm_expand: int = 2              # mamba d_inner = expand * d_model
    conv_kernel: int = 4             # mamba / RG-LRU temporal conv width
    lru_width: int = 0               # RG-LRU recurrent width (0 -> d_model)

    # --- encoder-decoder (whisper) -------------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 0             # stub frontend emits this many frames
    cross_attention: bool = False

    # --- modality frontend (stubbed per assignment) --------------------------
    frontend: str = ""               # "" | "audio_stub" | "vision_stub"

    # --- norms / misc ---------------------------------------------------------
    norm_eps: float = 1e-6
    post_norms: bool = False         # gemma2-style pre+post block norms
    emb_scale: bool = False          # gemma2 scales embeddings by sqrt(d_model)
    max_seq_len: int = 524288        # RoPE table cap

    # --- paper-technique applicability metadata ------------------------------
    subquadratic: bool = False       # may run long_500k

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.family in ("hybrid",) and self.lru_width == 0:
            object.__setattr__(self, "lru_width", self.d_model)

    # ---- derived -------------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def layer_kind(self, i: int) -> str:
        return self.block_pattern[i % len(self.block_pattern)]

    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        hd, nh, nkv = self.head_dim, self.n_heads, self.n_kv_heads
        n = v * d                                   # embedding
        if not self.tie_embeddings:
            n += v * d
        for i in range(self.n_layers):
            kind = self.layer_kind(i)
            if kind == "mamba":
                di, st = self.d_inner, self.ssm_state
                dtr = -(-d // 16)                   # mamba dt_rank
                n += d * di * 2                     # in_proj (x, z)
                n += di * self.conv_kernel + di     # depthwise conv
                n += di * (dtr + 2 * st)            # x_proj
                n += dtr * di + di                  # dt_proj + bias
                n += di * st + 2 * di               # A_log, D, dt_bias
                n += di * d                         # out_proj
            elif kind == "recurrent":
                w = self.lru_width
                n += d * w * 2 + w * self.conv_kernel + w * d + 3 * w
            else:                                   # attention
                n += d * (nh * hd) + 2 * d * (nkv * hd) + (nh * hd) * d
            if kind != "mamba" and ff > 0:
                nmat = 3 if self.mlp_gated else 2
                if self.is_moe:
                    n += self.n_experts * 3 * d * ff + d * self.n_experts
                else:
                    n += nmat * d * ff
            n += 2 * d                              # norms
        for _ in range(self.encoder_layers):
            n += 4 * d * d + 3 * d * ff + 2 * d
            if self.cross_attention:
                n += 4 * d * d
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts)."""
        if not self.is_moe:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        total = self.param_count()
        dense_ff = self.n_layers * self.topk * 3 * d * ff
        all_ff = self.n_layers * self.n_experts * 3 * d * ff
        return total - all_ff + dense_ff


# ---------------------------------------------------------------------------
# CNN zoo (the paper's own evaluation models)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ConvSpec:
    kind: str                        # conv | dwc | pool | add_branch
    out_ch: int = 0
    kernel: int = 3
    stride: int = 1
    repeat: int = 1
    expand: int = 0                  # inverted-residual expansion factor


@dataclass(frozen=True)
class CNNConfig:
    name: str
    input_hw: int
    input_ch: int
    stem_kernel: int
    stem_stride: int
    stem_ch: int
    stages: Tuple[ConvSpec, ...]
    num_classes: int = 1000
    gops: float = 0.0                # paper-reported GOPs per inference


# ---------------------------------------------------------------------------
# The DPUV4E engine configuration (the paper's technique as a feature)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EngineConfig:
    # Quantization mode for projection/conv compute:
    #   none -> bf16/f32 math (training path)
    #   w8   -> int8 weights, bf16 activations (weight-only)
    #   w8a8 -> int8 x int8 -> int32 (the paper's mode)
    #   w4a8 -> w8a8 everywhere, except LM projection weights pack to
    #           per-group int4 (Q4Tensor) dequantized in-register by the
    #           Conv-PE GEMM -- the weight-bandwidth decode mode
    quant: str = "none"
    # Rows per (scale, zero) group along K for w4a8 packing.  Lives here so
    # it keys ProgramCache entries (EngineConfig is part of ProgramKey):
    # w4/w8 programs -- and different group sizes -- never collide.
    w4_group_size: int = 64
    # Kernel backend: "ref" = pure-jnp oracle path (also the dry-run path:
    # XLA-TPU fuses the same epilogues), "pallas" = Pallas TPU kernels
    # (interpreted on the CPU backend only: kernels/ops.interpret_pallas).
    backend: str = "ref"
    # Paper features (each maps to a paper contribution; see DESIGN.md):
    use_dwc_engine: bool = True      # C4  DWC PE
    use_low_channel_unit: bool = True# C5  first-layer unit
    misc_on_engine: bool = True      # C6  fused eltwise/pool epilogues
    cascade_bk: int = 0              # C2  K-block (0 = DSE-chosen)
    block_m: int = 0                 # DSE-chosen when 0
    block_n: int = 0
    # XVDPU-analog baseline (paper's comparison target): unfused epilogue,
    # no DWC engine, no low-channel unit.
    baseline: bool = False
    # Beyond-paper serving features:
    kv_cache_dtype: str = "bf16"     # bf16 | int8
    act_quant: str = "dynamic"       # dynamic | static per-tensor act scales
    # Beyond-paper distribution feature: local (per-dp-shard) MoE dispatch.
    # 0 = global dispatch (baseline); N>1 = route tokens within N groups
    # whose leading axis matches the dp sharding, so the argsort/one-hot
    # routing machinery never crosses shards (see EXPERIMENTS.md §Perf).
    moe_local_groups: int = 0

    def resolved(self) -> "EngineConfig":
        if not self.baseline:
            return self
        return dataclasses.replace(
            self, use_dwc_engine=False, use_low_channel_unit=False,
            misc_on_engine=False)


# ---------------------------------------------------------------------------
# Assigned input shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524288, 1,   "decode"),
}


# ---------------------------------------------------------------------------
# Training configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    z_loss: float = 1e-4
    # Memory / schedule
    remat: str = "block"             # none | block | full
    microbatches: int = 1            # gradient accumulation
    loss_chunk_vocab: int = 0        # chunked-vocab CE (0 = off)
    scan_layers: bool = False        # lax.scan over stacked layer groups
    triangle_skip: bool = False      # exact-triangle causal attention
    param_dtype: str = "f32"         # f32 | bf16 (mixed precision: bf16
                                     # params+grads, f32 Adam moments)
    # Distribution
    zero1: bool = True               # shard optimizer state over data axis
    seq_shard_activations: bool = False  # SP between blocks (beyond-paper)
    # Fault tolerance
    ckpt_every: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    async_ckpt: bool = True
    keep_ckpts: int = 3
    step_timeout_s: float = 0.0      # straggler watchdog (0 = off)
    seed: int = 0
