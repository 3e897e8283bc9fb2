"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``; input shapes as
``ShapeConfig``; the federated-unlearning runtime as ``FLConfig``; and the
whole run (arch x shape x mesh x fl) as a ``RunConfig``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters.

    ``family`` selects the block stack:
      dense   -- decoder-only transformer (GQA)
      moe     -- decoder-only transformer with MoE FFN
      hybrid  -- interleaved attention + mamba blocks (+ optional MoE FFN)
      ssm     -- attention-free RWKV-6 stack
      vlm     -- decoder LM consuming a vision-patch prefix (frontend stub)
      audio   -- encoder-decoder consuming mel-frame embeddings (frontend stub)
      cnn     -- the paper's small conv classifier (CPU experiments only)
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    source: str = ""   # citation bracket from the assignment

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0          # per-expert hidden dim (0 -> d_ff)
    moe_every: int = 1         # MoE FFN on every k-th layer (others dense d_ff)
    moe_impl: str = "einsum"   # einsum (one-hot dispatch) | gather (index-based)
    moe_capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # DeepSeek-V3 gate (arXiv:2412.19437 Sec 2.1.2): "softmax" is top-k of a
    # softmax, renormalised, with the aux loss; "sigmoid" is noaux_tc with
    # one group: top-k of sigmoid scores plus a per-expert correction bias
    # (selection only), the chosen scores normalised and scaled by
    # ``moe_routed_scale``, every token kept (no capacity), no aux loss.
    moe_gate: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_shared_d_ff: int = 0   # shared experts as one SwiGLU of this width
    # experts held here under expert parallelism: [0, experts_held) of the
    # router's num_experts (0 = all); only their part of the layer is computed
    experts_held: int = 0
    first_dense_layers: int = 0  # leading layers with a dense d_ff FFN

    # --- multi-head latent attention (DeepSeek-V2; layer kind "mla") ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0  # one RoPE key shared by the heads
    v_head_dim: int = 0

    # --- LoRA adapters (arXiv:2106.09685) over a frozen base ---
    # clients train only rank-``lora_rank`` adapters on the four MLA
    # projections (scaled by lora_alpha / lora_rank); 0 = full-model training
    lora_rank: int = 0
    lora_alpha: float = 0.0

    # --- attention pattern ---
    # Repeating pattern of layer kinds; entries in {"global","local","mamba","rwkv"}.
    # The stack is pattern tiled to num_layers (remainder unrolled).
    layer_pattern: Tuple[str, ...] = ("global",)
    sliding_window: int = 4096
    rope_theta: float = 10_000.0
    attn_block_skip: bool = False   # §Perf: triangle-only causal blocks
    attn_block_q: int = 512         # q tile; 0 = whole seq (seq-parallel mode)
    ssm_chunk_dtype: str = "float32"  # §Perf: mamba chunk internals dtype
    mamba_impl: str = "chunked"       # chunked (XLA) | pallas (fused TPU kernel)

    # --- ssm / rwkv ---
    ssm_state_dim: int = 16        # mamba d_state
    ssm_conv_width: int = 4        # mamba conv1d width
    ssm_expand: int = 2            # mamba d_inner = expand * d_model
    rwkv_head_dim: int = 64
    rwkv_impl: str = "chunked"     # chunked (XLA) | pallas (fused wkv kernel)

    # --- norm / misc ---
    norm_type: str = "rmsnorm"     # rmsnorm | layernorm | nonparametric
    norm_eps: float = 1e-6
    act: str = "silu"              # silu | gelu
    tie_embeddings: bool = False
    logit_softcap: float = 0.0

    # --- encoder-decoder (audio) ---
    encoder_layers: int = 0
    decoder_context: int = 0       # architectural max decoder len (0 = unlimited)

    # --- frontends (stub per assignment carve-out) ---
    frontend: str = ""             # "" | "vision" | "audio"
    vision_tokens: int = 256       # VLM patch-prefix length

    # --- cnn (paper model) ---
    cnn_channels: Tuple[int, ...] = (16, 32)
    image_size: int = 28
    image_channels: int = 1
    num_classes: int = 10

    # --- numerics ---
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.num_experts and self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)

    # -- derived ----------------------------------------------------------
    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Expand layer_pattern to the stack's entries (``num_layers`` less
        the leading dense layers)."""
        pat = self.layer_pattern
        n = self.layers_in_stack
        reps = (n + len(pat) - 1) // len(pat)
        return tuple((pat * reps)[:n])

    def param_count(self) -> int:
        """Analytic parameter count (matches init within rounding)."""
        d, h, kv, hd = self.d_model, self.num_heads, self.num_kv_heads, self.head_dim
        n = self.vocab_size * d                      # embed
        if not self.tie_embeddings and self.family != "cnn":
            n += self.vocab_size * d                 # unembed
        kinds = self.layer_kinds
        n += self.first_dense_layers * (self._mla_params() + 3 * d * self.d_ff
                                        + 2 * self._norm_params())
        for i, kind in enumerate(kinds):
            if kind == "mla":
                n += self._mla_params() + self._ffn_params(i) + 2 * self._norm_params()
            elif kind in ("global", "local"):
                n += d * (h * hd) + 2 * d * (kv * hd) + (h * hd) * d  # q,k,v,o
                n += self._ffn_params(i)
                n += 2 * self._norm_params()
            elif kind == "mamba":
                di = self.ssm_expand * self.d_model
                n += d * 2 * di            # in_proj (x and z)
                n += di * self.ssm_conv_width
                n += di * (2 * self.ssm_state_dim + 1)  # B,C,dt projections (x-dep)
                n += di + di               # dt bias, A (diag per-channel x state folded)
                n += di * self.ssm_state_dim  # A matrix (diag over channels x state)
                n += di * d                # out proj
                n += self._norm_params()
                n += self._ffn_params(i) + self._norm_params()  # hybrid: ffn too
            elif kind == "rwkv":
                n += 4 * d * d             # r,k,v,g (time mix)
                n += d * d                 # output
                n += 2 * d                 # decay base, bonus u
                n += 5 * d + 32 * d * 2    # token-shift mixers + lora-ish decay proj
                n += int(d * self.d_ff) + int(self.d_ff * d)  # channel-mix
                n += 2 * self._norm_params()
        if self.family == "audio":
            for _ in range(self.encoder_layers):
                n += 4 * d * (h * hd) + self._ffn_params() + 2 * self._norm_params()
            # decoder cross-attention
            n += len(kinds) * (4 * d * (h * hd) + self._norm_params())
        n += self._norm_params()           # final norm
        return n

    def ffn_is_moe(self, layer_idx: int) -> bool:
        return bool(self.num_experts) and (layer_idx % self.moe_every == self.moe_every - 1)

    @property
    def serves(self) -> bool:
        """Whether every layer kind has a prefill and decode path (MLA has
        its training form only)."""
        return "mla" not in self.layer_pattern

    @property
    def layers_in_stack(self) -> int:
        """Layers after the leading dense ones (the pattern's layers)."""
        return self.num_layers - self.first_dense_layers

    def _mla_params(self) -> int:
        d, h, r = self.d_model, self.num_heads, self.kv_lora_rank
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (d * h * qk + d * (r + self.qk_rope_head_dim) + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)

    def _ffn_params(self, layer_idx: int = 0) -> int:
        if self.ffn_is_moe(layer_idx):
            e, f = self.num_experts, self.moe_d_ff
            n = self.d_model * e + (self.experts_held or e) * (3 * self.d_model * f)
            if self.moe_gate == "sigmoid":
                n += e                                       # correction bias
            return n + 3 * self.d_model * self.moe_shared_d_ff
        return 3 * self.d_model * self.d_ff  # gated mlp (gate,up,down)

    def lora_params(self) -> int:
        """Adapter parameters: rank x (in + out) of each of the four MLA
        projections, per layer."""
        d, h, r = self.d_model, self.num_heads, self.kv_lora_rank
        nope, rope, v = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        sizes = ((d, h * (nope + rope)),       # q_proj
                 (d, r + rope),                # kv_a_proj_with_mqa
                 (r, h * (nope + v)),          # kv_b_proj
                 (h * v, d))                   # o_proj
        return self.lora_rank * sum(map(sum, sizes)) * self.num_layers

    def _norm_params(self) -> int:
        return 0 if self.norm_type == "nonparametric" else self.d_model

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only top-k experts)."""
        if not self.num_experts:
            return self.param_count()
        full = self.param_count()
        e, k, f, d = self.num_experts, self.experts_per_token, self.moe_d_ff, self.d_model
        n_moe_layers = sum(1 for i in range(self.num_layers) if self.ffn_is_moe(i))
        unused = n_moe_layers * (e - k) * (3 * d * f)
        return full - unused


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Federated learning / unlearning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FLConfig:
    num_clients: int = 100          # C (paper Sec 5.1)
    clients_per_round: int = 20     # sampled per training round
    num_shards: int = 4             # S
    local_epochs: int = 10          # L
    global_rounds: int = 30         # G
    retrain_ratio: float = 2.0      # r  (retraining uses L/r local epochs)
    coded: bool = True              # coded vs uncoded sharding
    mu: float = 0.1                 # tolerated erroneous-slice fraction
    # dry-run FL step parameters (production archs):
    fl_clients_per_step: int = 4    # clients folded into one fedavg round
    fl_local_steps: int = 1         # local steps per client per round
    client_mode: str = "serial"     # serial (scan) | parallel (vmap)

    @property
    def clients_per_shard(self) -> int:
        return self.clients_per_round // self.num_shards


# ---------------------------------------------------------------------------
# Training / serving runtime
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"        # adamw | sgdm | adamw_bf16
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    momentum: float = 0.9
    grad_clip: float = 1.0


@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class ShardingConfig:
    """Logical-axis -> mesh-axis rule set."""
    # parameter axes
    tensor_axes: Tuple[str, ...] = ("model",)        # mlp/heads/expert/vocab
    fsdp_axes: Tuple[str, ...] = ()                  # embed dim of params
    # activation axes
    batch_axes: Tuple[str, ...] = ("data",)
    kvseq_axes: Tuple[str, ...] = ()                 # decode long-context KV seq
    # policy knobs
    remat: str = "block"                             # none | block | full
    scan_layers: bool = True
    shard_optimizer: bool = True


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = MeshConfig()
    sharding: ShardingConfig = ShardingConfig()
    fl: FLConfig = FLConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Reduced variants for CPU smoke tests
# ---------------------------------------------------------------------------

def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """2 layers, d_model<=512, <=4 experts — same family/block wiring."""
    d = min(cfg.d_model, 256)
    heads = min(cfg.num_heads, 4)
    kv = min(cfg.num_kv_heads, heads)
    head_dim = max(d // heads, 16)
    # keep the layer pattern's first two kinds so hybrid wiring is exercised
    kinds = cfg.layer_kinds[:2] if cfg.num_layers >= 2 else cfg.layer_kinds
    if cfg.family == "hybrid":
        kinds = ("global", "mamba")  # make sure both block types are hit
    if cfg.family == "ssm":
        kinds = ("rwkv", "rwkv")
    return dataclasses.replace(
        cfg,
        num_layers=2,
        d_model=d,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=head_dim,
        d_ff=min(cfg.d_ff, 512),
        moe_d_ff=min(cfg.moe_d_ff, 256) if cfg.num_experts else 0,
        vocab_size=min(cfg.vocab_size, 512),
        num_experts=min(cfg.num_experts, 4),
        experts_per_token=min(cfg.experts_per_token, 2),
        layer_pattern=kinds,
        encoder_layers=min(cfg.encoder_layers, 2),
        sliding_window=min(cfg.sliding_window, 64),
        vision_tokens=min(cfg.vision_tokens, 16),
        rwkv_head_dim=min(cfg.rwkv_head_dim, max(d // 4, 16)),
        param_dtype="float32",
        compute_dtype="float32",
    )
