"""Architecture + shape configuration schema.

One `ArchConfig` per assigned architecture lives in `configs/<id>.py`; the
paper's own SNN workloads are in `configs/snn_workloads.py`.  Shape cells are
the assignment's four input-shape sets.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    n_heads: int = 0            # 0 => attention-free
    n_kv: int = 0
    head_dim: int = 128
    act: str = "swiglu"         # swiglu | geglu | sq_relu | gelu
    qk_norm: bool = False
    attn: str = "causal"        # causal | bidir | swa
    window: int = 4096          # SWA window (and the sliding layers' window)
    # Per-layer attention kind, Hugging Face's ``layer_types``: each entry
    # "sliding_attention" (causal, last `window` positions) or
    # "full_attention"; the first n_layers entries apply.  Empty: every
    # layer runs `attn`.  Every layer keeps one full-length KV cache.
    layer_types: tuple[str, ...] = ()
    # GQA x TP: when n_kv doesn't divide the model axis but n_heads does,
    # expand K/V to all heads at use time (Megatron-style KV replication) so
    # attention intermediates stay head-sharded.  Measured 250x memory-term
    # reduction on nemotron train_4k (EXPERIMENTS.md §Perf).
    expand_kv: bool = False
    rope_theta: float = 500000.0
    # YaRN RoPE on the full-attention layers of a `layer_types` model
    # (Hugging Face's rope_type "yarn"); 0 = off.  ``yarn_attention_factor``
    # 0 takes YaRN's default, 0.1 ln(factor) + 1.
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 0.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # MoE
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25   # dense experts only: spiking ones drop none
    d_expert: int = 0               # expert width; 0 = d_ff

    # SSM (mamba2 / rwkv6)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4

    # hybrid (zamba2): one weight-shared attention block applied every
    # `shared_attn_every` backbone layers.
    shared_attn_every: int = 0

    # vlm: number of image tokens prepended (frontend stubbed: precomputed
    # patch embeddings are a model input).
    n_img_tokens: int = 0
    # audio: frontend stubbed: precomputed frame embeddings are the input.
    embed_inputs: bool = True   # False => inputs are (B, S, d_model) floats
    encoder_only: bool = False

    # Spiking dual-sparse FFN (the paper's technique; DESIGN.md §4).
    spiking_ffn: bool = False
    spiking_T: int = 4
    spiking_weight_density: float = 1.0

    # Distribution / memory policy.
    optimizer: str = "adamw"    # adamw | adafactor
    remat: bool = True
    scan_layers: bool = True
    scan_unroll: int = 1        # >1 interleaves layer collectives w/ compute
    fsdp: bool = False          # shard weights over (data, model) jointly
    seq_shard_activations: bool = True  # SP: shard residual carries
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # vocab-softmax token chunking (0 = off).  2048 measured 10.7 GiB/device
    # cheaper than 8192 on llama3.2-1b train_4k (EXPERIMENTS.md §Perf).
    loss_chunk: int = 2048
    attn_chunk: int = 512       # query chunking for attention (0 = off)
    ssm_chunk: int = 128        # recurrence chunk (remat boundary)

    # Shape-cell applicability.
    supports_decode: bool = True
    subquadratic: bool = False  # may run long_500k

    def n_params(self) -> int:
        """Analytic parameter count (embeddings included once if tied)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        p = 0
        if self.embed_inputs:
            p += V * D
        if not self.tie_embeddings and not self.encoder_only:
            p += D * V
        if self.encoder_only:
            p += D * V  # classifier head
        per_layer = 0
        if self.family in ("dense", "audio", "vlm", "moe"):
            if self.n_heads:
                per_layer += D * self.n_heads * self.head_dim      # q
                per_layer += 2 * D * self.n_kv * self.head_dim     # k, v
                per_layer += self.n_heads * self.head_dim * D      # o
            n_mats = 3 if self.act in ("swiglu", "geglu") else 2
            ffn = n_mats * D * (self.expert_width if self.n_experts else F)
            if self.n_experts:
                per_layer += self.n_experts * ffn + D * self.n_experts
            else:
                per_layer += ffn
            per_layer += 2 * D  # norms
        elif self.family == "ssm":
            if self.name.startswith("rwkv"):
                # time-mix: r,k,v,g,o (5 DxD) + low-rank decay; channel-mix 2
                per_layer += 5 * D * D + 2 * D * F + D * 64 * 2
            else:
                d_in = self.ssm_expand * D
                per_layer += D * (2 * d_in + 2 * self.ssm_state) + d_in * D
        elif self.family == "hybrid":
            d_in = self.ssm_expand * D
            per_layer += 2 * D * d_in  # in_proj (x, z)
            per_layer += d_in * (2 * self.ssm_state)  # B, C proj
            per_layer += d_in * D  # out proj
        p += L * per_layer
        if self.family == "hybrid" and self.shared_attn_every:
            # one shared attention+MLP block
            p += 2 * D * self.n_heads * self.head_dim + 2 * D * self.n_kv * self.head_dim
            p += 3 * D * F
        return p

    def active_params(self) -> int:
        """Active (per-token) params — differs from n_params for MoE."""
        if not self.n_experts:
            return self.n_params()
        D, F, L = self.d_model, self.expert_width, self.n_layers
        n_mats = 3 if self.act in ("swiglu", "geglu") else 2
        inactive = L * (self.n_experts - self.top_k) * n_mats * D * F
        return self.n_params() - inactive

    @property
    def expert_width(self) -> int:
        """Each expert's FFN width."""
        return self.d_expert or self.d_ff


@dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def applicable_shapes(cfg: ArchConfig) -> dict[str, ShapeCell | None]:
    """Which of the four shape cells run for this arch; None = skip + reason
    recorded by the dry-run manifest."""
    out: dict[str, ShapeCell | None] = {}
    for name, cell in SHAPES.items():
        if cell.kind == "decode" and (cfg.encoder_only or not cfg.supports_decode):
            out[name] = None
        elif name == "long_500k" and not cfg.subquadratic:
            out[name] = None
        else:
            out[name] = cell
    return out


def skip_reason(cfg: ArchConfig, shape: str) -> str:
    if shape in ("decode_32k", "long_500k") and cfg.encoder_only:
        return "encoder-only arch has no decode step"
    if shape == "long_500k" and not cfg.subquadratic:
        return "pure full-attention arch; 500k decode needs sub-quadratic attention"
    return ""


def smoke_variant(cfg: ArchConfig) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests (assignment: reduced
    layers/width/experts/vocab, one forward/train step, no NaNs)."""
    repl: dict = dict(
        n_layers=2,
        d_model=64,
        d_ff=128,
        vocab=512,
        loss_chunk=0,
        attn_chunk=32,
        ssm_chunk=8,
        window=16,
    )
    if cfg.n_heads:
        repl.update(n_heads=4, n_kv=max(1, min(cfg.n_kv, 2)), head_dim=16)
    if cfg.n_experts:
        repl.update(n_experts=4, top_k=2)
    if cfg.ssm_heads:
        # keep ssm_heads * ssm_head_dim == ssm_expand * d_model (hybrid) or
        # == d_model (rwkv)
        d_in = (cfg.ssm_expand if cfg.family == "hybrid" else 1) * 64
        repl.update(ssm_heads=d_in // 16, ssm_state=8, ssm_head_dim=16)
    if cfg.shared_attn_every:
        repl.update(shared_attn_every=1, n_layers=3)
    if cfg.n_img_tokens:
        repl.update(n_img_tokens=8)
    return dataclasses.replace(cfg, **repl)
