"""Model-family registry — adapters that unlock the model zoo for federated
scenarios.

Each ``ModelFamily`` builds a CPU-trainable ``ModelConfig`` from ``configs/``
(reduced where the source arch is production-scale), declares the task kind
it plays (``classification`` / ``generation``), and names the Pallas kernel
ops its forward routes through — the mamba adapter trains through the
``ssm_scan`` kernel (``mamba_impl="pallas"``) and the rwkv6 adapter through
the ``wkv`` kernel (``rwkv_impl="pallas"``), both in interpret mode off-TPU
with oracle-VJP backward passes.  Families register under one or more names
(``@register_model_family``), mirroring ``STORES`` / ``FRAMEWORKS`` /
``TASKS``: a new architecture reaches ``run_scenario`` → ``FederatedSession``
→ coded store → SE unlearning by subclassing + decorating, no simulator
surgery.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Type

from repro.configs import ModelConfig, get_config


class ModelFamily:
    """Base class for family adapters.  Subclass, implement ``build``, and
    register with ``@register_model_family(name, *aliases)``."""

    name: str = ""
    task: str = "generation"            # task kind this family plays
    kernel_ops: Tuple[str, ...] = ()    # Pallas ops the forward routes through
    default_lr: Optional[float] = None  # None -> the task's default
    default_batch: Optional[int] = None

    def build(self, cfg) -> ModelConfig:
        """Build the family's ``ModelConfig`` for one ``ScenarioConfig``."""
        raise NotImplementedError


FAMILIES: Dict[str, Type[ModelFamily]] = {}


def register_model_family(*names: str):
    """Class decorator registering a ``ModelFamily`` under ``names`` (the
    first is canonical)."""
    if not names:
        raise ValueError("register_model_family needs at least one name")

    def deco(cls: Type[ModelFamily]) -> Type[ModelFamily]:
        cls.name = names[0]
        for n in names:
            FAMILIES[n] = cls
        return cls
    return deco


def get_model_family(name: str) -> ModelFamily:
    try:
        return FAMILIES[name]()
    except KeyError:
        raise ValueError(f"unknown model family {name!r}; registered: "
                         f"{sorted(FAMILIES)}") from None


def canonical_families() -> Tuple[str, ...]:
    """The registered families, one name per class, sorted."""
    return tuple(sorted({cls.name for cls in FAMILIES.values()}))


# ---------------------------------------------------------------------------
# Built-in adapters
# ---------------------------------------------------------------------------

_TINY_LM = dict(num_layers=2, d_model=32, d_ff=64, vocab_size=109,
                param_dtype="float32", compute_dtype="float32")


@register_model_family("cnn")
class CNNFamily(ModelFamily):
    """The paper's conv classifier (Sec 5.1) at the CPU-container scale —
    bit-identical to the pre-registry ``_build_image`` model."""

    task = "classification"

    def build(self, cfg) -> ModelConfig:
        return dataclasses.replace(get_config("cnn-paper"),
                                   image_size=cfg.image_size, d_model=48,
                                   cnn_channels=(8, 16))


@register_model_family("transformer", "nanogpt")
class TransformerFamily(ModelFamily):
    """The paper's NanoGPT (4L, d=16, vocab 109) — bit-identical to the
    pre-registry ``_build_lm`` model."""

    task = "generation"

    def build(self, cfg) -> ModelConfig:
        return get_config("nanogpt-paper")


@register_model_family("mamba")
class MambaFamily(ModelFamily):
    """Selective-SSM stack (jamba-style mamba blocks) routed through the
    fused ``ssm_scan`` Pallas kernel — interpret mode on CPU, the real
    kernel on TPU."""

    task = "generation"
    kernel_ops = ("ssm_scan",)
    default_lr = 0.1

    def build(self, cfg) -> ModelConfig:
        return ModelConfig(name="mamba-fl", family="hybrid",
                           layer_pattern=("mamba",), num_heads=4,
                           num_kv_heads=4, ssm_state_dim=8, ssm_expand=2,
                           mamba_impl="pallas", norm_type="layernorm",
                           act="gelu", source="scenario zoo (mamba)",
                           **_TINY_LM)


@register_model_family("rwkv6", "rwkv")
class RWKV6Family(ModelFamily):
    """Attention-free RWKV-6 stack routed through the ``wkv`` Pallas kernel
    (interpret mode on CPU)."""

    task = "generation"
    kernel_ops = ("wkv",)
    default_lr = 0.1

    def build(self, cfg) -> ModelConfig:
        return ModelConfig(name="rwkv6-fl", family="ssm",
                           layer_pattern=("rwkv",), num_heads=2,
                           num_kv_heads=2, rwkv_head_dim=16,
                           rwkv_impl="pallas", norm_type="layernorm",
                           act="silu", source="scenario zoo (rwkv6)",
                           **_TINY_LM)


@register_model_family("moe")
class MoEFamily(ModelFamily):
    """Mixture-of-experts FFN transformer (granite-style top-k routing) —
    per-client expert specialization under label/quantity skew."""

    task = "generation"
    default_lr = 0.1

    def build(self, cfg) -> ModelConfig:
        return ModelConfig(name="moe-fl", family="moe", num_heads=4,
                           num_kv_heads=2, num_experts=4,
                           experts_per_token=2, moe_d_ff=32,
                           norm_type="rmsnorm", act="silu",
                           source="scenario zoo (moe)", **_TINY_LM)


@register_model_family("moonlight")
class MoonlightLoRAFamily(ModelFamily):
    """Moonlight-16B-A3B's block (MLA, a leading dense layer, a DeepSeek-V3
    gate over 8 experts of which 4 are held, one shared expert) at a CPU
    size, federating rank-4 LoRA adapters on the attention projections over
    a frozen base: the tiny variant of ``moonlight-16b-a3b-fedlora``."""

    task = "generation"
    default_lr = 0.1

    def build(self, cfg) -> ModelConfig:
        return dataclasses.replace(
            get_config("moonlight-16b-a3b-fedlora"),
            name="moonlight-fl", num_layers=2, d_model=64, num_heads=4,
            num_kv_heads=4, head_dim=16, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, d_ff=96, moe_d_ff=32,
            moe_shared_d_ff=32, num_experts=8, experts_per_token=3,
            experts_held=4, vocab_size=109, lora_rank=4, lora_alpha=8.0,
            param_dtype="float32", compute_dtype="float32",
            source="scenario zoo (moonlight, tiny)")
