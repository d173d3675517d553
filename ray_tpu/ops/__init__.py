from .attention import (
    attention_reference,
    flash_attention,
    ring_attention,
    ulysses_attention,
)
from .moe import MoEConfig, moe_forward, moe_init, moe_router
from .norms import layernorm, rmsnorm
from .rope import apply_rope, rope_frequencies, rotate_half

__all__ = [
    "flash_attention",
    "ring_attention",
    "ulysses_attention",
    "attention_reference",
    "rmsnorm",
    "layernorm",
    "apply_rope",
    "rotate_half",
    "rope_frequencies",
    "MoEConfig",
    "moe_init",
    "moe_forward",
    "moe_router",
]
