"""GPT model family (GPT-2 / GPT-J / Llama-style), TPU-first.

Design (vs the reference's torch models driven through Train/DeepSpeed —
`release/air_examples/gptj_deepspeed_finetuning`):
  * pure-functional pytree params — no module framework between the math and
    pjit; shardings come from `ShardingRules` logical dims.
  * ONE stacked layer pytree + `lax.scan` over the layer axis → constant
    compile time in depth, XLA pipelines the remat.
  * attention is pluggable: "flash" (Pallas), "ring" (sp-axis sequence
    parallel), "ulysses", "ref" — long context is a config flag, not a fork.
  * bf16 params/activations, f32 optimizer state & softmax stats.

Flagship configs: `gpt2_*` (LayerNorm/GELU/learned-pos), `gptj_6b`
(parallel block + rotary), `llama_7b`-style (RMSNorm/SwiGLU/rotary),
`smallthinker_21b_a3b` (grouped-query heads, window and global layers,
dropless top-k experts; served through the paged programs), `ouro_2_6b`
(sandwich norms, the layer stack run four times over shared weights with an
exit gate; served through the paged programs), `ax_k1` (latent attention
over one compressed cache row a token, a leading dense layer, sigmoid-routed
experts of which this chip holds a range, beside a shared expert; served
through the paged programs), `jamba2_3b` (state-space layers whose state is
one fixed slot a sequence, an attention layer every fourteenth; served
through the paged programs), `laguna_xs2` (window layers of 64 query heads
beside full layers of 48 over the same 8 K/V heads, a rotary table a kind, a
gate a head, a leading dense layer, 256 sigmoid-routed experts beside a
shared one; served through the paged programs).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import flash_attention, layernorm, paged_attention, ring_attention, rmsnorm, rope_frequencies, rotate_half
from ..ops.attention import attention_reference, heads_a_step, ulysses_attention
from ..parallel.mesh import ShardingRules


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # padded to a multiple of 128 for the MXU
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_head: int = 64
    d_mlp: int = 3072
    max_seq: int = 1024
    # Grouped-query attention: K/V heads, each shared by n_heads/n_kv_heads
    # query heads. None = n_heads (multi-head, the fused w_qkv layout).
    n_kv_heads: Optional[int] = None
    # Architecture knobs.
    norm: str = "layernorm"          # layernorm | rmsnorm
    activation: str = "gelu"  # gelu | swiglu | reglu (relu-gated) | relu2 (relu squared, NO gate: block_pattern)
    pos: str = "learned"             # learned | rotary | none (no positional term)
    rotary_dim: int = 64
    rope_theta: float = 10000.0
    # Per-layer kinds (None = every layer alike), one entry a layer, as the
    # published configs give them: rope_layout 1 = rotary, 0 = NO positional
    # term at all (needs pos="rotary"); sliding_window_layout 1 = the layer
    # attends to the last `sliding_window` keys (query i sees keys j with
    # i - window < j <= i), 0 = global. Where all layers share parameter
    # shapes the kind rides the layer scan as data. JSON lists become tuples.
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window: int = 0
    # Window layers whose SHAPES are their own, on where `n_heads_window` > 0:
    # a window layer has that many query heads (over the same K/V heads) and
    # its own rotary table (`window_rotary_dim` features of a head at
    # `window_rope_theta`, never scaled; 0 = as the global layers'), while
    # `n_heads`, `rotary_dim`, `rope_theta` and `rope_scaling` describe the
    # global layers. The attention weights are then two stacks BY KIND (`w_q`,
    # `w_kv`, `w_o`, `w_head_gate` [global layers, ...] beside `win_<name>`
    # [window layers, ...]) under one stack of norms and MLPs, and the layer
    # loop is cut into runs of one kind (`_mixed_layers`). No bias anywhere.
    n_heads_window: int = 0
    window_rotary_dim: int = 0
    window_rope_theta: float = 0.0
    # One gate a head a token: head n's attention output times
    # sigmoid(h W_g)[n] before the output projection, h the normed input
    # that q, k and v read (`w_head_gate` [L, E, heads]).
    attn_gate: bool = False
    parallel_block: bool = False     # GPT-J: attn and mlp in parallel
    # Sandwich norms: a second norm on each sublayer's OUTPUT before it joins
    # the stream, x + N2(Attn(N1 x)) then a + N4(Mlp(N3 a)).
    sandwich_norm: bool = False
    # Layers run several times (a looped model): the whole stack `ut_steps`
    # times over the SAME weights, the final norm closing every pass and its
    # output the next pass's input, a one-unit exit gate read after each
    # (`_close_pass`); each (pass, layer) pair keeps its own keys and values
    # (`kv_layout`). Served by `forward` and the paged programs; every token
    # runs every pass, whatever the gate reads.
    ut_steps: int = 1
    # Latent attention (MLA), on where `kv_lora_rank` > 0: queries through a
    # normed bottleneck of `q_lora_rank`; a token's keys and values through
    # ONE normed latent row of `kv_lora_rank` plus `rotary_dim` rotary
    # features every head shares, which is all a layer caches (`KVLayout`).
    # A head is `d_head` features without position (its values are as wide)
    # beside `rotary_dim` with it: the published `qk_nope_head_dim` =
    # `v_head_dim` and `qk_rope_head_dim`. `_block` hands `attend` the
    # ABSORBED operands (`_project_latent`); `n_kv_heads` is not read.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    # The published `rope_scaling` group of a YaRN model as (name, value)
    # pairs (`factor`, `original_max_position_embeddings`, `beta_fast`,
    # `beta_slow`, `mscale`, `mscale_all_dim`): `_rope_tables` interpolates
    # the slow dimensions, `_latent_scale` carries mscale squared. A JSON
    # object becomes sorted pairs.
    rope_scaling: Optional[Tuple[Tuple[str, float], ...]] = None
    # The first `dense_layers` of the `n_layers` have a dense gated MLP of
    # `d_dense_mlp` whatever `mlp_type` says (`first_k_dense_replace`,
    # `intermediate_size`): their weights lie beside the scanned stack under
    # `lead_<name>` and run through the same `_block` before the scan.
    dense_layers: int = 0
    d_dense_mlp: int = 0
    # State-space layers (`ops/ssm.py`), their state one slot a SEQUENCE (`KVLayout.state`), no bias.
    # `ssm_layout` (Mamba-1): 1 = the layer's mixer is the state-space one, a stack of its own
    # (`_mixed_layers`). `block_pattern` (Mamba-2 among blocks of ONE mixer): the end of this file.
    ssm_layout: Optional[Tuple[int, ...]] = None
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 160
    block_pattern: Optional[str] = None  # a block a character: M Mamba-2 | E experts | * attention
    ssm_heads: int = 0                   # Mamba-2: heads of `ssm_head_dim` channels, B and C shared
    ssm_head_dim: int = 0                # by `ssm_groups` groups of heads, the chunked form's chunk
    ssm_groups: int = 1; ssm_chunk: int = 128; gdn_interval: int = 0  # > 0: gated delta-net layers, every `gdn_interval`-th gated attention: the END of this file
    moe_select_bias: bool = False; layer_pattern: Optional[str] = None  # by score + a bias | a layer a character, (mw)+mf(gc)+: the END of this file
    norm_eps: float = 1e-6               # the RMSNorms of a `block_pattern` model (1e-6 elsewhere)
    tie_embeddings: bool = True
    # Mixture-of-Experts (expert parallelism over the ep mesh axis).
    mlp_type: str = "dense"          # dense | moe
    moe_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # capacity: GShard top-1/top-2 with capacity dropping (training).
    # dropless: top-k for any k, softmax over the kept logits, no token
    # dropped, gated experts (ops/moe.py), the router fed from the layer's
    # own input before the first norm ("router ahead of attention") -- the
    # routing the paged path serves.
    moe_routing: str = "capacity"
    # Dropless routing's data. Scoring: "softmax" over the kept logits |
    # "sigmoid" of every logit, the kept scores over their sum, times
    # `moe_route_scale`. What the router reads: "block" (the layer's input,
    # before the first norm) | "mlp" (the normed MLP input). `moe_shared`:
    # always-on gated experts of `d_mlp` each, added to the routed sum.
    # `moe_held` = (first, count): the range of the `moe_experts` routed
    # experts THIS program holds (a chip's share of a layer): the router
    # keeps its width and its top-k, the weight stacks are [L, count, ...],
    # and an assignment to an absent expert adds nothing (no exchange).
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    moe_router_in: str = "block"
    moe_shared: int = 0
    moe_held: Optional[Tuple[int, int]] = None
    # Execution knobs.
    dtype: Any = jnp.bfloat16
    # The dtype `init_params` makes the tree in. float32: masters for the
    # optimizer, cast a layer at a time. With bfloat16 that cast is no
    # operation and a serving program streams 2 bytes a parameter.
    param_dtype: Any = jnp.float32
    # How `init_params` scales random weights. "gpt2": std 0.02, residual
    # projections over sqrt(2L). "unit_stream": a matrix of fan-in n has std
    # gain / sqrt(n), its gain from `init_gains` (`_init_unit_stream`), which
    # the preset states: (name, gain) pairs.
    init: str = "gpt2"               # gpt2 | unit_stream
    init_gains: Tuple[Tuple[str, float], ...] = ()
    attn_impl: str = "flash"         # flash | ring | ulysses | ref
    remat: bool = True
    # None (save nothing) | "dots" | "attn" (save flash attention's out+lse
    # so backward never re-runs the VPU-bound forward kernel — the costliest
    # recompute per the r4 profile; +~32 MB/layer at B=12,S=1024).
    remat_policy: Optional[str] = None
    sp_axis: str = "sp"

    def __post_init__(self):
        for name in ("rope_layout", "sliding_window_layout", "ssm_layout"):
            v = getattr(self, name)
            if v is not None:
                v = tuple(int(bool(e)) for e in v)
                if len(v) != self.n_layers:
                    raise ValueError(
                        f"{name} has {len(v)} entries for {self.n_layers} layers")
                object.__setattr__(self, name, v)
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"n_heads {self.n_heads} not a multiple of n_kv_heads {self.kv_heads}")
        if self.sliding_window_layout and any(self.sliding_window_layout) \
                and self.sliding_window < 1:
            raise ValueError("window layers need sliding_window >= 1")
        if self.rope_layout is not None and self.pos != "rotary":
            raise ValueError('rope_layout needs pos="rotary"')
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps {self.ut_steps}: at least one pass")
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(
                (k, float(v)) for k, v in self.rope_scaling.items() if k != "type")))
        elif self.rope_scaling is not None:
            object.__setattr__(self, "rope_scaling", tuple(
                (k, float(v)) for k, v in self.rope_scaling))
        if self.moe_held is not None:
            first, count = (int(v) for v in self.moe_held)
            if not 0 <= first < first + count <= self.moe_experts:
                raise ValueError(f"moe_held {self.moe_held}: a range of the "
                                 f"{self.moe_experts} routed experts")
            object.__setattr__(self, "moe_held", (first, count))
        if self.moe_scoring not in ("softmax", "sigmoid") \
                or self.moe_router_in not in ("block", "mlp"):
            raise ValueError("moe_scoring: softmax | sigmoid; moe_router_in: block | mlp")
        if self.kv_lora_rank and (self.pos != "rotary" or not self.q_lora_rank):
            raise ValueError('latent attention (kv_lora_rank) needs pos="rotary" '
                             "and a query bottleneck (q_lora_rank)")
        if self.dense_layers and (
                not 0 < self.dense_layers < self.n_layers or self.d_dense_mlp < 1
                or self.ut_steps > 1
                or (self.layer_kinds is not None and not self.n_heads_window)):
            raise ValueError(
                "dense_layers: fewer than n_layers, of width d_dense_mlp, in a "
                "one-pass model whose layers are of one attention kind (or of "
                "two kinds by stack, n_heads_window)")
        if self.n_heads_window and (
                self.rope_layout is not None or self.pos != "rotary"
                or self.ut_steps > 1 or self.kv_lora_rank or self.ssm_layout
                or self.kv_heads == self.n_heads or self.n_heads_window % self.kv_heads
                or self.sandwich_norm or self.parallel_block
                or self.activation not in ("swiglu", "reglu")
                or len(set((self.sliding_window_layout or (0,))[self.dense_layers:])) < 2
                or any(self.sliding_window_layout[:self.dense_layers])):
            raise ValueError(
                "n_heads_window: a one-pass rotary model of grouped-query heads "
                "with a gated MLP or gated experts, window layers and global "
                "layers both behind global leading dense layers")
        if (self.window_rotary_dim or self.window_rope_theta) and not self.n_heads_window:
            raise ValueError("a window layer's own rotary table needs n_heads_window")
        if self.ssm_layout and (
                self.layer_kinds is not None or self.ut_steps > 1 or self.kv_lora_rank
                or self.dense_layers or self.mlp_type != "dense" or self.sandwich_norm
                or self.parallel_block or self.pos == "learned"
                or self.activation not in ("swiglu", "reglu")):
            raise ValueError(
                "ssm_layout: a one-pass model of plain (grouped-query) attention "
                "layers without learned positions, a dense gated MLP in every layer")
        if self.sandwich_norm and self.parallel_block:
            raise ValueError("sandwich_norm norms each sublayer's output; a parallel_block has one sum")
        _check_block_pattern(self); _check_layer_pattern(self); _check_gdn(self)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def layer_heads(self) -> Tuple[int, ...]:
        """[L] query heads of each layer."""
        win = self.sliding_window_layout or (0,) * self.n_layers
        return tuple(self.n_heads_window if w and self.n_heads_window else self.n_heads
                     for w in win)

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this program holds."""
        return self.moe_held[1] if self.moe_held else self.moe_experts

    @property
    def layer_kinds(self):
        """None when every layer is alike, else ([L] rotary flags, [L]
        windows, 0 = global) as the layouts give them."""
        if self.rope_layout is None and not (
                self.sliding_window_layout and any(self.sliding_window_layout)):
            return None
        L = self.n_layers
        rope = self.rope_layout or (int(self.pos == "rotary"),) * L
        win = self.sliding_window_layout or (0,) * L
        return rope, tuple(self.sliding_window * w for w in win)

    @property
    def moe_config(self):
        from ..ops.moe import MoEConfig

        return MoEConfig(
            num_experts=self.moe_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            d_model=self.d_model,
            d_ff=self.d_mlp,
            aux_loss_weight=self.moe_aux_weight,
            activation=self.activation,
            dtype=self.dtype,
        )

    @property
    def n_params(self) -> int:
        """Parameters HELD (a `moe_held` range counts its own experts)."""
        if self.block_pattern or self.layer_pattern or self.gdn_interval: return (_pattern_params if self.block_pattern else _sambay_params if self.layer_pattern else _gdn_params)(self)
        E, L, F, V, Hd = self.d_model, self.n_layers, self.d_mlp, self.vocab_size, self.n_heads * self.d_head
        gated = self.activation in ("swiglu", "reglu")
        if self.mlp_type == "moe":
            n_mats = 3 if gated else 2
            mlp_params = ((self.held_experts + self.moe_shared) * n_mats * E * F
                          + E * self.moe_experts)
        else:
            mlp_params = (2 if gated else 1) * E * F + F * E
        attn = E * (Hd + 2 * self.kv_heads * self.d_head) + Hd * E
        if self.kv_lora_rank:
            Rq, Rkv, Hr = self.q_lora_rank, self.kv_lora_rank, self.n_heads * self.rotary_dim
            attn = (E * Rq + Rq + Rq * (Hd + Hr) + E * (Rkv + self.rotary_dim) + Rkv
                    + Rkv * 2 * Hd + Hd * E)
        norms = (4 if self.sandwich_norm else 2) * E
        if self.ssm_layout:     # no bias anywhere; two mixers, one MLP shape
            Di, N, R = self.ssm_inner, self.ssm_state, self.ssm_dt_rank
            ssm = (E * 2 * Di + Di * self.ssm_conv + Di + Di * (R + 2 * N) + R + 2 * N
                   + R * Di + Di + Di * N + Di + Di * E)
            n_ssm = sum(self.ssm_layout)
            return (n_ssm * ssm + (L - n_ssm) * attn + L * (3 * E * F + 2 * E)
                    + V * E + E + (0 if self.tie_embeddings else E * V))
        attn_all = L * attn
        if self.n_heads_window or self.attn_gate:   # each layer's own heads, the gate
            attn_all = sum(2 * E * self.d_head * (h + self.kv_heads)
                           + self.attn_gate * E * h for h in self.layer_heads)
        total = (attn_all + L * norms + (L - self.dense_layers) * mlp_params
                 + self.dense_layers * 3 * E * self.d_dense_mlp
                 + V * E + (0 if self.tie_embeddings else E * V))
        total += (E + 1) * (self.ut_steps > 1)  # the exit gate
        if self.n_heads_window:
            total += E      # counted whole: the final norm too
        if self.pos == "learned":
            total += self.max_seq * E
        return total


# Canonical configs ---------------------------------------------------------
def gpt2_small(**kw):
    return GPTConfig(**{**dict(n_layers=12, d_model=768, n_heads=12, d_mlp=3072), **kw})


def gpt2_medium(**kw):
    return GPTConfig(**{**dict(n_layers=24, d_model=1024, n_heads=16, d_mlp=4096), **kw})


def gpt2_large(**kw):
    return GPTConfig(**{**dict(n_layers=36, d_model=1280, n_heads=20, d_mlp=5120), **kw})


def gpt2_xl(**kw):
    return GPTConfig(**{**dict(n_layers=48, d_model=1600, n_heads=25, d_mlp=6400), **kw})


def gptj_6b(**kw):
    return GPTConfig(
        **{
            **dict(
                n_layers=28,
                d_model=4096,
                n_heads=16,
                d_head=256,
                d_mlp=16384,
                vocab_size=50432,
                pos="rotary",
                rotary_dim=64,
                parallel_block=True,
                tie_embeddings=False,
                max_seq=2048,
            ),
            **kw,
        }
    )


def llama_7b(**kw):
    return GPTConfig(
        **{
            **dict(
                n_layers=32,
                d_model=4096,
                n_heads=32,
                d_head=128,
                d_mlp=11008,
                vocab_size=32000,
                norm="rmsnorm",
                activation="swiglu",
                pos="rotary",
                rotary_dim=128,
                tie_embeddings=False,
                max_seq=2048,
            ),
            **kw,
        }
    )


def smallthinker_21b_a3b(**kw):
    """SmallThinker-21BA3B-Instruct (huggingface.co/PowerInfer): 28 query
    heads over 4 K/V heads of 128, one global layer WITHOUT positional
    encoding then three rotary layers with a window of 4,096, in every
    layer 64 ReLU-gated experts of 768, top-6 without drops, the router
    fed from the layer's input; weights held in bfloat16. Serving only
    (the paged programs and `forward` with attn_impl="ref")."""
    L = kw.get("n_layers", 52)
    kinds = tuple(int(l % 4 != 0) for l in range(L))
    return GPTConfig(
        **{
            **dict(
                n_layers=L,
                d_model=2560,
                n_heads=28,
                n_kv_heads=4,
                d_head=128,
                d_mlp=768,
                vocab_size=151936,
                max_seq=16384,
                norm="rmsnorm",
                activation="reglu",
                pos="rotary",
                rotary_dim=128,
                rope_theta=1500000.0,
                rope_layout=kinds,
                sliding_window_layout=kinds,
                sliding_window=4096,
                tie_embeddings=False,
                mlp_type="moe",
                moe_experts=64,
                moe_top_k=6,
                moe_routing="dropless",
                param_dtype=jnp.bfloat16,
                # The embedding has std 1.5, so the stream keeps the token's
                # identity (the vector every position shares stays under a
                # sixth of it); attention logits of std q * k = 3.6 (a
                # handful of keys carry a query's weight, so which keys a
                # layer may see matters), router logits of the stream's own
                # size (unequal gate weights), and twelve layers that
                # together add somewhat more than the embedding
                # (`scripts/smallthinker_tolerance.py` reads it on the chip).
                init="unit_stream",
                init_gains=(("embed", 1.5), ("q", 1.9), ("k", 1.9), ("v", 1.0),
                            ("o", 0.9), ("router", 1.0), ("mlp_in", 1.0),
                            ("mlp_out", 0.5), ("head", 1.0)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


def ouro_2_6b(**kw):
    """Ouro-2.6B (huggingface.co/ByteDance/Ouro-2.6B, arXiv:2510.25741): 48
    layers of 16 heads of 128 (plain multi-head, rotary over the whole head,
    theta 1e6), SiLU-gated MLP of 5632, sandwich RMSNorms, the whole stack
    run `total_ut_steps` = 4 times over the same weights with an exit gate
    after each pass (at the published `early_exit_threshold` of 1.0 every
    token runs all four); untied head, weights held in bfloat16. Serving
    only: `forward` (attn_impl="ref") and the paged programs."""
    return GPTConfig(
        **{
            **dict(
                n_layers=48,
                d_model=2048,
                n_heads=16,
                n_kv_heads=16,
                d_head=128,
                d_mlp=5632,
                vocab_size=49152,
                max_seq=65536,
                norm="rmsnorm",
                activation="swiglu",
                pos="rotary",
                rotary_dim=128,
                rope_theta=1000000.0,
                sandwich_norm=True,
                ut_steps=4,
                tie_embeddings=False,
                param_dtype=jnp.bfloat16,
                # A sublayer's output is normed on its way into the stream,
                # so the scale of `w_o` and `w_out` is moot: what a layer
                # adds is its post-norm's WEIGHT, here a constant `post_norm`.
                # 2 x 48 additions of that size come to about twice the
                # pass's unit-size input (the embedding has std 1), so a pass
                # rewrites most of the stream and keeps a third of what it
                # was given: greedy tokens then depend on every pass and on
                # which cache a pass reads. `q` and `k` give attention scores
                # of std 1.2^2 = 1.4: 192 layer applications are a long
                # product of Jacobians, and under scores of std 3.6 (q, k
                # 1.9, a near-argmax over the keys) bfloat16's rounding grew
                # through them until the engine's tokens read as far from
                # the float32 reference as a wrong model's (token error
                # 0.5-0.7 against 0.7-1.4; at 1.2 at most 0.016 against a
                # thinnest control of 0.094, 12 seeds: my chip runs, PR 32,
                # `scripts/ouro_tolerance.py`). Attention that soft makes
                # greedy tokens repeat more (5-31 distinct of 96).
                init="unit_stream",
                init_gains=(("embed", 1.0), ("q", 1.2), ("k", 1.2), ("v", 1.0),
                            ("o", 1.0), ("mlp_in", 1.0), ("mlp_out", 1.0),
                            ("post_norm", 0.2), ("exit_gate", 1.0), ("head", 1.0)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


def ax_k1(**kw):
    """A.X-K1 (huggingface.co/skt/A.X-K1, `model_type: "axk1"`; the layer is
    DeepSeek-V3's): latent attention (queries through a normed bottleneck of
    1536, keys and values through ONE normed latent row of 512 beside 64
    rotary features all 64 heads share: 576 numbers a token a layer are all
    that is cached), YaRN rotary (factor 32 over 4,096), one leading dense
    layer (SiLU-gated MLP of 18432), then layers of 192 sigmoid-routed
    experts of 2048 (top-8, the kept scores normalised, x 2.5, the router on
    the normed MLP input) beside one shared expert; untied head, bfloat16.
    Serving only: `forward` (attn_impl="ref") and the paged programs. 519 B
    parameters whole: a chip serves its share of a deployment (`moe_held`, a
    slice of the vocabulary, some of the layers), which the caller states."""
    return GPTConfig(
        **{
            **dict(
                n_layers=61,
                dense_layers=1,
                d_model=7168,
                n_heads=64,
                d_head=128,
                q_lora_rank=1536,
                kv_lora_rank=512,
                d_mlp=2048,
                d_dense_mlp=18432,
                vocab_size=163840,
                max_seq=131072,
                norm="rmsnorm",
                activation="swiglu",
                pos="rotary",
                rotary_dim=64,
                rope_theta=10000.0,
                rope_scaling=(("beta_fast", 32.0), ("beta_slow", 1.0), ("factor", 32.0),
                              ("mscale", 1.0), ("mscale_all_dim", 1.0),
                              ("original_max_position_embeddings", 4096.0)),
                tie_embeddings=False,
                mlp_type="moe",
                moe_experts=192,
                moe_top_k=8,
                moe_routing="dropless",
                moe_scoring="sigmoid",
                moe_route_scale=2.5,
                moe_router_in="mlp",
                moe_shared=1,
                param_dtype=jnp.bfloat16,
                # As for `smallthinker_21b_a3b` (under std 0.02 a deep random
                # stack decodes one token whatever its layers do): the
                # embedding has std 1.5; both latents are normed, so the
                # down-projections' scales (`dq`, `dkv`) are moot; queries and
                # keys of std 1.5 over 192 features under the scale 192^-0.5 x
                # mscale^2 = 0.131 give scores of std 4.1 (a handful of keys
                # carry a query's weight, so a wrong scale or a rotation on
                # the wrong columns moves tokens); router logits of std 2
                # (the kept sigmoid scores all near 1, so the eight weights
                # are near 0.31 each, where a softmax would spread them
                # tenfold). Every MLP's output has gain 0.5: a routed expert
                # adds 0.31 x 0.6 x 0.5 = 0.09 of the stream's unit where it
                # lands (the held range sees one of a token's eight
                # assignments every other layer). How they were chosen (token
                # errors of the benchmark's own check, my chip runs, PR 34,
                # `scripts/axk1_tolerance.py --init-gains`): at a routed
                # output gain of 1 a top-8 near-tie that bfloat16 decides
                # otherwise than float32 moved a checked token as far as
                # float8 weights do (sound up to 0.048, float8 from 0.049, 12
                # seeds); at 0.25 a softmax in the sigmoid's place no longer
                # showed (0.019 against a sound 0.008); with q, k 1.3 float8
                # read 0.046-0.093 beside a sound tail of 0.034 (18 seeds);
                # with 1.7 bfloat16 itself diverged (sound up to 0.116); at
                # 1.5 the sound engine reads at most 0.048 (31 seeds) and
                # float8 from 0.129 (18 seeds). No program's shape or time
                # depends on the numbers.
                init="unit_stream",
                init_gains=(("embed", 1.5), ("dq", 1.0), ("q", 1.5), ("dkv", 1.0),
                            ("k", 1.5), ("v", 1.0), ("o", 0.9), ("router", 2.0),
                            ("mlp_in", 1.0), ("mlp_out", 0.5), ("head", 1.0)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


def jamba2_3b(**kw):
    """AI21-Jamba2-3B (huggingface.co/ai21labs/AI21-Jamba2-3B, `model_type:
    "jamba"`): 28 layers of 2560, layer i an attention layer where i % 14 ==
    7 (20 query heads over ONE K/V head of 128, no positional term at all),
    else a Mamba-1 mixer (inner width 5120, state 16, convolution 4, step
    rank 160, three inner RMSNorms); every layer's MLP the dense SiLU-gated
    one of 8192 (`num_experts` 1); RMSNorm 1e-6; vocabulary 65,536 TIED; no
    bias anywhere. Serving only: `forward` (attn_impl="ref") and the paged
    programs, whole on one chip."""
    L = kw.get("n_layers", 28)
    return GPTConfig(
        **{
            **dict(
                n_layers=L,
                d_model=2560,
                n_heads=20,
                n_kv_heads=1,
                d_head=128,
                d_mlp=8192,
                vocab_size=65536,
                max_seq=262144,
                norm="rmsnorm",
                activation="swiglu",
                pos="none",
                ssm_layout=tuple(int(l % 14 != 7) for l in range(L)),
                ssm_state=16,
                ssm_conv=4,
                ssm_expand=2,
                ssm_dt_rank=160,
                tie_embeddings=True,
                param_dtype=jnp.bfloat16,
                # As for `smallthinker_21b_a3b`: a stream that keeps the
                # token (embedding std 1) under layers that together add
                # about twice as much. The state-space mixer's own numbers
                # follow the family's published initialisation: `A_log` the
                # log of 1..16, `b_dt` the inverse softplus of steps drawn
                # log-uniform in 0.001-0.1, `D` 1, the step's projection of
                # std `ssm_dt` / sqrt(rank). With steps that small a state
                # decays over some 6-100 tokens and what it adds to the
                # mixer's output beside the skip term D c is a sum over 16
                # states of products B_t C_t' of two normed vectors: the
                # gains of those two inner norms (`ssm_bc`, 3 each) make it
                # the larger part, so that a state zeroed at a chunk's edge,
                # a tail dropped or a padding token scanned moves the greedy
                # tokens (`scripts/jamba_tolerance.py` reads each on the
                # chip). The mixer's output is a product of five factors
                # that each depend on its input (step, c, B, C, gate): at
                # `ssm_out` 0.9 a mixer added 1.4 to a stream of 1-7 and 26
                # of them grew a rounding 60-80fold (a sound bfloat16 engine
                # read as far from float32 as float8 weights); at 0.2 a
                # mixer adds 0.3, the stream ends at 2.2 (a fifth of its
                # power the token's embedding) and a rounding grows 7fold.
                # `q`, `k` 1.2 as `ouro_2_6b`; `o` 1.0 and `mlp_out` 0.35
                # keep an attention layer's and an MLP's share beside it.
                init="unit_stream",
                init_gains=(("embed", 1.0), ("q", 1.2), ("k", 1.2), ("v", 1.0),
                            ("o", 1.0), ("mlp_in", 1.0), ("mlp_out", 0.35),
                            ("ssm_in", 1.0), ("ssm_conv", 1.0), ("ssm_x", 1.0),
                            ("ssm_bc", 3.0), ("ssm_dt", 1.0), ("ssm_out", 0.2)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


def laguna_xs2(**kw):
    """Laguna-XS.2 (huggingface.co/poolside/Laguna-XS.2, `model_type:
    "laguna"`, 33.4 B parameters, 3 B active): 40 layers of 2048, 8 K/V heads
    of 128 in every layer; layer l a FULL attention layer of 48 query heads
    where l % 4 == 0 (rotary over the first 64 features of a head, theta
    500,000, YaRN factor 64 over 4,096 positions, cos and sin times 1.4159)
    and else a window layer of 64 query heads (the last 512 keys; rotary over
    the whole head, theta 10,000, unscaled); one sigmoid gate a head a token
    on the attention output; layer 0's MLP dense SiLU-gated of 8192, every
    other layer 256 sigmoid-routed experts of 512 (top-8, the kept scores
    normalised, x 2.5, the router on the normed MLP input) beside one shared
    expert; RMSNorm 1e-6, untied head, no bias, bfloat16. Serving only:
    `forward` (attn_impl="ref") and the paged programs; a chip serves a
    pipeline stage of it (`n_layers`, which the caller states)."""
    L = kw.get("n_layers", 40)
    return GPTConfig(
        **{
            **dict(
                n_layers=L,
                dense_layers=1,
                d_model=2048,
                n_heads=48,
                n_heads_window=64,
                n_kv_heads=8,
                d_head=128,
                d_mlp=512,
                d_dense_mlp=8192,
                vocab_size=100352,
                max_seq=262144,
                norm="rmsnorm",
                activation="swiglu",
                pos="rotary",
                rotary_dim=64,
                rope_theta=500000.0,
                rope_scaling=(("attention_factor", 1.4158883083359672),
                              ("beta_fast", 64.0), ("beta_slow", 1.0), ("factor", 64.0),
                              ("original_max_position_embeddings", 4096.0)),
                window_rotary_dim=128,
                window_rope_theta=10000.0,
                sliding_window_layout=tuple(int(l % 4 != 0) for l in range(L)),
                sliding_window=512,
                attn_gate=True,
                tie_embeddings=False,
                mlp_type="moe",
                moe_experts=256,
                moe_top_k=8,
                moe_routing="dropless",
                moe_scoring="sigmoid",
                moe_route_scale=2.5,
                moe_router_in="mlp",
                moe_shared=1,
                param_dtype=jnp.bfloat16,
                # `ax_k1`'s gains (the same router, the same shared expert,
                # the same scale 2.5) but for the ROUTED experts' output, a
                # gain of its own (`expert_out` 0.1 where the dense MLP and
                # the shared expert keep `mlp_out` 0.5), and a gate logit of
                # std 1, so that a head's gate lies between 0.27 and 0.73 for
                # two tokens of three and a model without it reads otherwise.
                # Scores of std 1.5^2 = 2.25 in a window layer and, with cos
                # and sin times 1.416 over the rotated half of a head, 3.6 in
                # a full one: a handful of keys carry a query's weight, so a
                # key at the window's edge and a rotation by the other kind's
                # table move tokens. Why `expert_out`: of 256 sigmoid scores
                # the eighth and the ninth largest lie 0.1 apart in the mean,
                # so bfloat16's rounding of the router's input swaps them in
                # one token-layer of five to ten; all 256 experts are held,
                # so every swap lands, and the kept scores are all near 1, so
                # a swap moves an eighth of the routed sum whatever the
                # rounding's size. At `expert_out` 0.5 that moved the sound
                # engine's tokens as far as float8 weights, a window a block
                # too wide or top-7 do (the benchmark's own token check on
                # the chip at the published widths, my chip runs, PR 43:
                # sound 0.004-0.116 over 26 seeds, float8 0.050-0.158, the
                # window 0.041-0.099, top-7 0.063-0.116); at 0.1 a swap moves
                # a fifth of that and the sound engine reads 0.002-0.006 where
                # float8 reads 0.019-0.051 and the window 0.019-0.032 (8
                # seeds). Top-7 for top-8 IS such a swap and is no longer
                # told apart (PERF.md 7 (2)). Tried beside it, 8 seeds each:
                # q, k 1.9 with `expert_out` 0.15 (sound up to 0.016, float8
                # from 0.026), embed 1.0 (0.010 | 0.022), `expert_out` 0.05
                # with router 1.0 (the window from 0.007). A rounding of 1e-3
                # at the embedding grows 34-35fold through the float32
                # reference's five layers at 0.5 (`--parts growth`).
                # `scripts/laguna_tolerance.py` reads each on the chip. No
                # program's shape or time depends on the numbers.
                init="unit_stream",
                init_gains=(("embed", 1.5), ("q", 1.5), ("k", 1.5), ("v", 1.0),
                            ("o", 0.9), ("gate", 1.0), ("router", 2.0),
                            ("mlp_in", 1.0), ("mlp_out", 0.5), ("expert_out", 0.1),
                            ("head", 1.0)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


CONFIGS = {
    "gpt2-small": gpt2_small,
    "gpt2-medium": gpt2_medium,
    "gpt2-large": gpt2_large,
    "gptj-6b": gptj_6b,
    "llama-7b": llama_7b,
    "smallthinker-21b-a3b": smallthinker_21b_a3b,
    "ouro-2.6b": ouro_2_6b,
    "ax-k1": ax_k1,
    "jamba2-3b": jamba2_3b,
    "laguna-xs2": laguna_xs2,
}


# ------------------------------------------------------------------- params
# The second norm of each sublayer under `sandwich_norm`, on its output.
_POST_NORM_KEYS = ("ln1_post_w", "ln1_post_b", "ln2_post_w", "ln2_post_b")


def param_logical_dims(cfg: GPTConfig) -> Dict[str, Tuple[Optional[str], ...]]:
    """Logical dims per parameter — feed through ShardingRules for shardings."""
    dims = {
        "tok_embed": ("vocab", "embed"),
        "ln_f_w": ("embed_act",),
        "ln_f_b": ("embed_act",),
        "w_qkv": ("layers", "embed", None, "heads", "head_dim"),
        "b_qkv": ("layers", None, "heads", "head_dim"),
        "w_o": ("layers", "heads", "head_dim", "embed"),
        "b_o": ("layers", "embed_act"),
        "ln1_w": ("layers", "embed_act"),
        "ln1_b": ("layers", "embed_act"),
    }
    if cfg.kv_heads != cfg.n_heads:
        del dims["w_qkv"], dims["b_qkv"]
        dims["w_q"] = ("layers", "embed", "heads", "head_dim")
        dims["w_kv"] = ("layers", "embed", None, "heads", "head_dim")
    if cfg.kv_lora_rank or cfg.dense_layers or cfg.moe_shared or cfg.ssm_layout \
            or cfg.n_heads_window or cfg.attn_gate or cfg.block_pattern or cfg.layer_pattern or cfg.gdn_interval:
        raise NotImplementedError(
            "no sharding is written for latent attention (kv_lora_rank), "
            "leading dense layers (dense_layers), a shared expert (moe_shared), "
            "state-space layers (ssm_layout), attention stacks by kind (n_heads_window), "
            "a gate a head (attn_gate), blocks of one mixer (block_pattern) or a decoder-hybrid-decoder (layer_pattern), gated delta-net layers (gdn_interval): they are "
            "served on one chip")
    if cfg.mlp_type == "moe":
        dims["moe_router"] = ("layers", "embed", "experts")
        dims["moe_w_in"] = ("layers", "experts", "embed", "mlp")
        dims["moe_w_out"] = ("layers", "experts", "mlp", "embed")
        if cfg.activation in ("swiglu", "reglu"):
            dims["moe_w_gate"] = ("layers", "experts", "embed", "mlp")
    else:
        dims["w_in"] = ("layers", "embed", "mlp")
        dims["b_in"] = ("layers", "mlp_act")
        dims["w_out"] = ("layers", "mlp", "embed")
        dims["b_out"] = ("layers", "embed_act")
        if cfg.activation in ("swiglu", "reglu"):
            dims["w_gate"] = ("layers", "embed", "mlp")
    if not cfg.parallel_block:
        dims["ln2_w"] = ("layers", "embed_act")
        dims["ln2_b"] = ("layers", "embed_act")
    if cfg.sandwich_norm:
        for name in _POST_NORM_KEYS:
            dims[name] = ("layers", "embed_act")
    if cfg.ut_steps > 1:
        dims["exit_gate_w"] = ("embed_act",)
        dims["exit_gate_b"] = ()
    if cfg.pos == "learned":
        dims["pos_embed"] = (None, "embed")
    if not cfg.tie_embeddings:
        dims["lm_head"] = ("embed", "vocab")
    return dims


# init="unit_stream": random weights under which greedy tokens depend on every
# mechanism of the layer, as they do in a trained model. With std 0.02 a deep
# random stack collapses: near-uniform attention and experts that every
# position then chooses alike add the same vector at every position, it
# outgrows the token's own embedding within three layers (97% of the final
# stream is one vector), and every position decodes one and the same token
# whatever the window, the positions, the routing or a pass did. Here the
# embedding has std `embed` and a matrix of fan-in n has std gain / sqrt(n),
# the gains from the preset (`GPTConfig.init_gains`, where each says why);
# under sandwich norms a post-norm's weight is the constant `post_norm`; the
# exit gate's logit has the size of `exit_gate`. Under a TIED head random
# weights score the input token by its own embedding's square, E x embed^2
# beside a best other logit of 4.7 x sqrt(E) x embed x |stream|, and greedy
# decoding repeats one token: the final norm's gain is +1 and -1 by turns
# there, so that the head the stream meets, the embedding times those signs,
# is as random as the embedding and at right angles to it in the mean (a
# trained tied model does not answer with its input either). The benchmark's
# token check rests on this; no program's shape or time depends on the numbers.
def _init_unit_stream(rng, cfg: GPTConfig) -> Dict[str, jnp.ndarray]:
    if cfg.activation not in ("swiglu", "reglu") or cfg.parallel_block \
            or cfg.pos == "learned" or (cfg.tie_embeddings and not cfg.ssm_layout) \
            or not cfg.init_gains:
        raise NotImplementedError(
            'init="unit_stream" covers models without learned positions, with a '
            "gated MLP or gated experts and an untied head (tied with state-space "
            "layers) whose preset states `init_gains`")
    E, F, V, X = cfg.d_model, cfg.d_mlp, cfg.vocab_size, cfg.held_experts
    L = cfg.n_layers - cfg.dense_layers         # the scanned stack's layers
    La = L - sum(cfg.ssm_layout or ())          # the attention stack's layers
    Lw = sum(cfg.sliding_window_layout) if cfg.n_heads_window else 0
    La -= Lw                                    # window layers: a stack of their own
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    k = jax.random.split(rng, 16)
    dt, g = cfg.param_dtype, dict(cfg.init_gains)

    def n(key, shape, gain, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain / math.sqrt(fan_in))).astype(dt)

    ones, zeros = (lambda: jnp.ones((L, E), dt)), (lambda: jnp.zeros((L, E), dt))
    params = {
        "tok_embed": n(k[0], (V, E), g["embed"], 1),
        "ln_f_w": (jnp.where(jnp.arange(E) % 2, -1, 1).astype(dt)
                   if cfg.tie_embeddings else jnp.ones((E,), dt)),
        "ln_f_b": jnp.zeros((E,), dt),
        "w_o": n(k[2], (La, H, Dh, E), g["o"], H * Dh), "b_o": zeros(),
        "ln1_w": ones(), "ln1_b": zeros(), "ln2_w": ones(), "ln2_b": zeros(),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = n(k[7], (E, V), g["head"], E)
    if cfg.ssm_layout:
        params.update(_init_ssm_stack(k[11], cfg, n))
    if cfg.kv_lora_rank:
        # Latent attention: both latents are normed (weights of one); the
        # shared rotary key columns of `w_dkv` and the per-head key half of
        # `w_ukv` carry `k`, its value half `v`.
        Rq, Rkv, Dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rotary_dim
        params.update({
            "w_dq": n(k[1], (L, E, Rq), g["dq"], E),
            "q_norm_w": jnp.ones((L, Rq), dt),
            "w_uq": n(k[9], (L, Rq, H, Dh + Dr), g["q"], Rq),
            "w_dkv": jnp.concatenate([n(k[10], (L, E, Rkv), g["dkv"], E),
                                      n(k[13], (L, E, Dr), g["k"], E)], axis=-1),
            "kv_norm_w": jnp.ones((L, Rkv), dt),
            "w_ukv": jnp.concatenate([n(k[14], (L, Rkv, H, Dh), g["k"], Rkv),
                                      n(k[15], (L, Rkv, H, Dh), g["v"], Rkv)], axis=-1),
        })
    else:
        q, kv = n(k[1], (La, E, H, Dh), g["q"], E), [
            n(k[9], (La, E, Hkv, Dh), g["k"], E), n(k[10], (La, E, Hkv, Dh), g["v"], E)]
        if Hkv != H:    # the layouts of `init_params`
            params.update({"w_q": q, "w_kv": jnp.stack(kv, axis=2)})
        else:
            params.update({"w_qkv": jnp.stack([q, *kv], axis=2),
                           "b_qkv": jnp.zeros((L, 3, H, Dh), dt)})
    if cfg.attn_gate:
        params["w_head_gate"] = n(k[13], (La, E, H), g["gate"], E)
    if cfg.n_heads_window:      # the window layers' attention, their own shapes
        kw, Hw = jax.random.split(k[14], 5), cfg.n_heads_window
        params.update({
            "win_w_q": n(kw[0], (Lw, E, Hw, Dh), g["q"], E),
            "win_w_kv": jnp.stack([n(kw[1], (Lw, E, Hkv, Dh), g["k"], E),
                                   n(kw[2], (Lw, E, Hkv, Dh), g["v"], E)], axis=2),
            "win_w_o": n(kw[3], (Lw, Hw, Dh, E), g["o"], Hw * Dh),
        })
        if cfg.attn_gate:
            params["win_w_head_gate"] = n(kw[4], (Lw, E, Hw), g["gate"], E)
    if cfg.mlp_type == "moe":
        params.update({
            "moe_router": n(k[3], (L, E, cfg.moe_experts), g["router"], E),
            "moe_w_in": n(k[4], (L, X, E, F), g["mlp_in"], E),
            "moe_w_gate": n(k[8], (L, X, E, F), g["mlp_in"], E),
            "moe_w_out": n(k[5], (L, X, F, E), g.get("expert_out", g["mlp_out"]), F),
        })
        if cfg.moe_shared:
            ks, Fs = jax.random.split(k[12], 3), cfg.moe_shared * F
            params.update({
                "shared_w_in": n(ks[0], (L, E, Fs), g["mlp_in"], E),
                "shared_w_gate": n(ks[1], (L, E, Fs), g["mlp_in"], E),
                "shared_w_out": n(ks[2], (L, Fs, E), g["mlp_out"], Fs),
            })
    else:
        params.update({
            "w_in": n(k[3], (L, E, F), g["mlp_in"], E), "b_in": jnp.zeros((L, F), dt),
            "w_gate": n(k[5], (L, E, F), g["mlp_in"], E),
            "w_out": n(k[4], (L, F, E), g["mlp_out"], F), "b_out": zeros(),
        })
    if cfg.sandwich_norm:
        for name in _POST_NORM_KEYS:
            params[name] = jnp.full((L, E), g["post_norm"], dt) \
                if name.endswith("_w") else zeros()
    if cfg.ut_steps > 1:
        params["exit_gate_w"] = n(k[11], (E,), g["exit_gate"], E)
        params["exit_gate_b"] = jnp.zeros((), dt)
    if cfg.dense_layers:    # the leading layers: the same block, a dense MLP
        lead = _init_unit_stream(jax.random.fold_in(k[12], 1), _lead_cfg(cfg))
        params.update({"lead_" + name: a for name, a in _layer_stack(lead).items()})
    if cfg.ssm_layout or cfg.n_heads_window:    # as published: no bias anywhere
        params = {name: a for name, a in params.items()
                  if name.removeprefix("lead_") not in _BIAS_KEYS}
    return params


_BIAS_KEYS = ("ln_f_b", "b_qkv", "b_o", "ln1_b", "ln2_b", "b_in", "b_out")


def _init_ssm_stack(rng, cfg: GPTConfig, n) -> Dict[str, jnp.ndarray]:
    """The state-space mixers' stack [layers of that kind, ...] under the
    names `_layer_stack` knows (`ssm_<name of ops/ssm.py>`): the family's
    published initialisation (`jamba2_3b` says which and why), matrices
    through `n` (key, shape, gain, fan-in). `A_log` is kept state-major, [N,
    Di], the published [Di, N] transposed: channels on the lanes."""
    E, Di, N, R, K = cfg.d_model, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    Ls, dt, g = sum(cfg.ssm_layout), cfg.param_dtype, dict(cfg.init_gains)
    k = jax.random.split(rng, 6)
    step = jnp.exp(jax.random.uniform(k[5], (Ls, Di), jnp.float32)
                   * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "ssm_w_in": n(k[0], (Ls, E, 2 * Di), g["ssm_in"], E),
        "ssm_conv_w": n(k[1], (Ls, K, Di), g["ssm_conv"], K),
        "ssm_conv_b": jnp.zeros((Ls, Di), dt),
        "ssm_w_x": n(k[2], (Ls, Di, R + 2 * N), g["ssm_x"], Di),
        "ssm_dt_norm_w": jnp.ones((Ls, R), dt),
        "ssm_b_norm_w": jnp.full((Ls, N), g["ssm_bc"], dt),
        "ssm_c_norm_w": jnp.full((Ls, N), g["ssm_bc"], dt),
        "ssm_w_dt": n(k[3], (Ls, R, Di), g["ssm_dt"], R),
        "ssm_b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "ssm_A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (Ls, N, Di)).astype(dt),
        "ssm_D": jnp.ones((Ls, Di), dt),
        "ssm_w_out": n(k[4], (Ls, Di, E), g["ssm_out"], Di),
    }


def _lead_cfg(cfg: GPTConfig) -> GPTConfig:
    """The config of the `dense_layers` leading layers as a model of their
    own: the same attention (the global layers', where the window layers
    have shapes of their own), a dense MLP of `d_dense_mlp`."""
    return dataclasses.replace(
        cfg, n_layers=cfg.dense_layers, dense_layers=0, mlp_type="dense",
        d_mlp=cfg.d_dense_mlp, sliding_window_layout=None, n_heads_window=0,
        window_rotary_dim=0, window_rope_theta=0.0)


def _window_cfg(cfg: GPTConfig) -> GPTConfig:
    """What `_block` and `_rope_tables` read of a WINDOW layer of a model
    whose kinds have shapes of their own (`n_heads_window`): its heads and
    its rotary table in the global layers' fields, everything else alike."""
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads_window, n_heads_window=0, dense_layers=0,
        rotary_dim=cfg.window_rotary_dim or cfg.rotary_dim,
        rope_theta=cfg.window_rope_theta or cfg.rope_theta,
        rope_scaling=None if cfg.window_rope_theta else cfg.rope_scaling,
        window_rotary_dim=0, window_rope_theta=0.0)


def _lead_stack(params):
    """The leading dense layers' stacked weights [dense_layers, ...] under
    the names `_block` reads."""
    return {k: params["lead_" + k] for k in _LAYER_KEYS if "lead_" + k in params}


def init_params(rng, cfg: GPTConfig) -> Dict[str, jnp.ndarray]:
    if cfg.init == "unit_stream":
        return (_init_pattern if cfg.block_pattern else _init_sambay if cfg.layer_pattern else _init_gdn if cfg.gdn_interval else _init_unit_stream)(rng, cfg)
    if cfg.init != "gpt2":
        raise ValueError(f"init {cfg.init!r}: gpt2 | unit_stream")
    if cfg.kv_lora_rank or cfg.dense_layers or cfg.moe_shared or cfg.moe_held \
            or cfg.ssm_layout or cfg.n_heads_window or cfg.attn_gate:
        raise NotImplementedError(
            'init="gpt2" makes no latent attention, leading dense layers, shared '
            'expert, held range of experts, state-space layers, attention stacks '
            'by kind or gate a head: such a preset states init="unit_stream"')
    E, L, F, V = cfg.d_model, cfg.n_layers, cfg.d_mlp, cfg.vocab_size
    H, Dh = cfg.n_heads, cfg.d_head
    k = jax.random.split(rng, 16)
    std = 0.02
    resid_std = std / math.sqrt(2 * L)
    # Master params live in f32 by default (optimizer precision); forward
    # casts each layer's weights to cfg.dtype (bf16) as the scan touches it.
    # A serving preset states bfloat16 and the cast is then no operation.
    dt = cfg.param_dtype

    def n(key, shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

    params = {
        "tok_embed": n(k[0], (V, E)),
        "ln_f_w": jnp.ones((E,), dt),
        "ln_f_b": jnp.zeros((E,), dt),
        "w_qkv": n(k[1], (L, E, 3, H, Dh)),
        "b_qkv": jnp.zeros((L, 3, H, Dh), dt),
        "w_o": n(k[2], (L, H, Dh, E), resid_std),
        "b_o": jnp.zeros((L, E), dt),
        "ln1_w": jnp.ones((L, E), dt),
        "ln1_b": jnp.zeros((L, E), dt),
    }
    if cfg.kv_heads != H:
        # Grouped-query layout: Q apart from the (fewer) K/V heads; no
        # projection biases (the fused multi-head layout keeps its own).
        del params["w_qkv"], params["b_qkv"]
        params["w_q"] = n(k[1], (L, E, H, Dh))
        params["w_kv"] = n(k[9], (L, E, 2, cfg.kv_heads, Dh))
    if cfg.mlp_type == "moe":
        X = cfg.moe_experts
        params["moe_router"] = n(k[3], (L, E, X))
        params["moe_w_in"] = n(k[4], (L, X, E, F))
        params["moe_w_out"] = n(k[5], (L, X, F, E), resid_std)
        if cfg.activation in ("swiglu", "reglu"):
            params["moe_w_gate"] = n(k[8], (L, X, E, F))
    else:
        params["w_in"] = n(k[3], (L, E, F))
        params["b_in"] = jnp.zeros((L, F), dt)
        params["w_out"] = n(k[4], (L, F, E), resid_std)
        params["b_out"] = jnp.zeros((L, E), dt)
        if cfg.activation in ("swiglu", "reglu"):
            params["w_gate"] = n(k[5], (L, E, F))
    if not cfg.parallel_block:
        params["ln2_w"] = jnp.ones((L, E), dt)
        params["ln2_b"] = jnp.zeros((L, E), dt)
    if cfg.sandwich_norm:
        for name in _POST_NORM_KEYS:
            params[name] = (jnp.ones if name.endswith("_w") else jnp.zeros)((L, E), dt)
    if cfg.ut_steps > 1:
        params["exit_gate_w"] = n(k[11], (E,))
        params["exit_gate_b"] = jnp.zeros((), dt)
    if cfg.pos == "learned":
        params["pos_embed"] = n(k[6], (cfg.max_seq, E))
    if not cfg.tie_embeddings:
        params["lm_head"] = n(k[7], (E, V))
    return params


def param_shardings(cfg: GPTConfig, mesh, rules: Optional[ShardingRules] = None):
    rules = rules or ShardingRules.default()
    dims = param_logical_dims(cfg)
    return {name: rules.sharding(mesh, *d) for name, d in dims.items()}


# ------------------------------------------------------------------ forward
def _norm(x, w, b, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, w)
    return layernorm(x, w, b)


def _attention(cfg: GPTConfig, q, k, v, mesh=None):
    """Two integration modes:

    * mesh=None (manual SPMD, or one device): caller wrapped the whole
      forward in shard_map, if at all; axis names are already bound — call
      the impl directly.
    * mesh given (automatic/pjit): everything else auto-partitions; only the
      attention core drops into a nested shard_map over the mesh, so the ring
      ppermutes ride the sp axis, and the flash kernels see one device's
      batch and heads, while XLA keeps handling dp/fsdp/tp.
    """
    if cfg.attn_impl == "ref":
        return attention_reference(q, k, v, causal=True)
    seq_axis = None
    if cfg.attn_impl in ("ring", "ulysses"):
        impl = ring_attention if cfg.attn_impl == "ring" else ulysses_attention
        impl = functools.partial(impl, axis=cfg.sp_axis, causal=True)
        seq_axis = cfg.sp_axis
    elif cfg.remat and cfg.remat_policy == "attn":
        from ..ops.attention import flash_attention_with_stats

        # The stats variant's vjp names its residuals ("attn_out"/"attn_lse")
        # so the "attn" remat policy saves them instead of re-running the
        # forward kernel; lse exists only for that purpose.
        def impl(q, k, v):
            return flash_attention_with_stats(q, k, v, causal=True)[0]
    else:
        impl = functools.partial(flash_attention, causal=True)
    if mesh is None:
        return impl(q, k, v)
    # The flash kernels need the wrap as much as the collectives do: GSPMD
    # cannot partition a Mosaic custom call, and left bare inside a
    # partitioned jit it would gather q/k/v onto every device. Batch and
    # heads are independent, so each device runs the kernel on its shard.
    from jax.sharding import PartitionSpec as P

    from ..parallel.spmd import shard_fn

    spec = P(("dp", "fsdp"), "tp", seq_axis, None)
    return shard_fn(impl, mesh, in_specs=(spec,) * 3, out_specs=spec)(q, k, v)


@jax.custom_vjp
def _flat_proj(h, w):
    """h [B, S, E] x w [E, F] -> [B, S, F], with the weights' gradient held
    apart from what becomes of it: see `_flat_proj_bwd`."""
    return jnp.einsum("bse,ef->bsf", h, w)


def _flat_proj_fwd(h, w):
    return _flat_proj(h, w), (h, w)


def _flat_proj_bwd(res, g):
    """The product's own two gradients, the weights' behind a barrier. Left
    to itself XLA folds the reshape of dw [E, F] to the parameter's [E, heads,
    Dh] INTO the product, which then wants g laid (heads, Dh)-major, and
    turns all of g, the kernels' dq, dk and dv, to [B, F, S] first (three of
    gpt2-large's eleven copies a layer: 34 MB each for a result of 3 MB;
    PERF.md §6, PR 49)."""
    h, w = res
    dw = jax.lax.optimization_barrier(jnp.einsum("bse,bsf->ef", h, g))
    return jnp.einsum("bsf,ef->bse", g, w), dw


_flat_proj.defvjp(_flat_proj_fwd, _flat_proj_bwd)


def _heads_flat(tokens: int, embed: int, heads: int, head_dim: int) -> bool:
    """Whether the projections to and from heads are said FLAT, over [B, S, heads·Dh], and
    not with heads and Dh named apart: where the flash kernels read and write that form
    (`ops/attention.py` `heads_a_step`: heads of 64 by the pair) AND the activations are the
    larger operand, more tokens than E. Named, a result is laid out in tiles of (heads, Dh),
    half of every tile empty at 64, and XLA took every array of a layer's attention through
    sequence-minor copies to get there and back (gpt2-large's train step: eleven a layer, 34
    MB each). Flat, it is the WEIGHTS that are reshaped, which a served program would first
    have to write out (PERF.md §6, PR 49)."""
    return tokens > embed and heads_a_step(heads, head_dim) > 0


def _project_qkv(cfg: GPTConfig, p, h):
    """h [B, S, E] -> q [B, S, H, Dh], k and v [B, S, Hkv, Dh]: the fused multi-head
    w_qkv (the form an engine holds: `_project_served`), or the grouped-query pair w_q /
    w_kv. Flat (`_heads_flat`): a product each, from a slice of the WEIGHTS (a slice of
    one product's result is a copy of the activations, and a reshape of [3, heads, Dh]
    cannot keep a sharding of the heads)."""
    if "w_qkv_served" in p:
        return _project_served(p, h)
    if "w_qkv" in p:
        (B, S, E), (H, D) = h.shape, p["w_qkv"].shape[2:]
        if _heads_flat(B * S, E, H, D):
            return tuple((_flat_proj(h, p["w_qkv"][:, t].reshape(E, H * D))
                          + p["b_qkv"][t].reshape(H * D)).reshape(B, S, H, D)
                         for t in range(3))
        qkv = jnp.einsum("bse,ethd->btshd", h, p["w_qkv"]) + p["b_qkv"][:, None]
        return qkv[:, 0], qkv[:, 1], qkv[:, 2]
    q = jnp.einsum("bse,ehd->bshd", h, p["w_q"])
    kv = jnp.einsum("bse,ethd->btshd", h, p["w_kv"])
    return q, kv[:, 0], kv[:, 1]


def _merge_heads(attn, w_o):
    """attn [B, heads, S, Dh] x w_o [heads, Dh, E] -> [B, S, E]; flat
    (`_heads_flat`), what the kernels wrote, [B, S, heads·Dh], is the
    product's operand as it lies."""
    B, H, S, D = attn.shape
    if _heads_flat(B * S, w_o.shape[-1], H, D):
        return jnp.einsum("bsf,fe->bse", attn.transpose(0, 2, 1, 3).reshape(B, S, H * D),
                          w_o.reshape(H * D, -1))
    return jnp.einsum("bhsd,hde->bse", attn, w_o)


def _dense_mlp(cfg: GPTConfig, p, mlp_in):
    u = jnp.einsum("bse,ef->bsf", mlp_in, p["w_in"]) + p["b_in"]
    if cfg.activation in ("swiglu", "reglu"):
        g = jnp.einsum("bse,ef->bsf", mlp_in, p["w_gate"])
        u = (jax.nn.silu(g) if cfg.activation == "swiglu" else jax.nn.relu(g)) * u
    else:
        u = jax.nn.gelu(u)
    return jnp.einsum("bsf,fe->bse", u, p["w_out"]) + p["b_out"]


def _dropless_mlp(cfg: GPTConfig, router, experts, router_in, mlp_in,
                  layer=None, valid=None, bias=None):
    """The dropless expert layer over [B, S, E]: float32 router on
    `router_in` (the layer's input or the normed MLP input, as
    `cfg.moe_router_in` says), top-k without capacity under the config's
    scoring, gated experts (ops/moe.py). `experts` = (w_gate, w_in,
    w_out), this layer's or with `layer` the whole stacks. A program that
    holds a range of the experts (`cfg.moe_held`) routes over all of them and
    keeps the combine matrix's columns of its own: what an absent expert
    would add is left out. The sum is the grouped form whatever the step's
    size (`moe.dropless_experts`): its cost is the row tiles the routing
    fills, and a token outside `valid` [B, S] (a padding lane, a chunk's
    padding) is routed nowhere, so it fills none. Returns (y, [2] f32 =
    experts touched and the busiest expert's share) of this layer's routing
    over the experts held, the valid tokens'; under `moe_held` [5]: also
    their assignments that fell on held experts, all of them, and 1 if none
    fell here (an empty routing: no expert is read)."""
    from ..ops import moe

    B, S, E = mlp_in.shape
    logits = router_in.reshape(B * S, E).astype(jnp.float32) @ router.astype(jnp.float32)
    idx, w = moe.dropless_route(logits, cfg.moe_top_k, cfg.moe_scoring,
                                cfg.moe_route_scale, bias)
    combine = moe.dropless_combine(idx, w, cfg.moe_experts)
    if cfg.moe_held:
        combine = combine[:, cfg.moe_held[0]: sum(cfg.moe_held)]
    if valid is not None:   # a padding token's output is thrown away: it routes nowhere
        valid = valid.reshape(B * S)
        combine = jnp.where(valid[:, None], combine, 0.0)
    y = moe.dropless_experts(
        mlp_in.reshape(B * S, E), combine, *experts, cfg.activation, layer=layer,
        grouped_k=cfg.moe_top_k)
    load = moe.dropless_load(combine, valid, cfg.moe_top_k if cfg.moe_held else 0)
    return y.reshape(B, S, E), jnp.stack(load)


def _gated_mlp(cfg: GPTConfig, x, w_gate, w_in, w_out):
    g = jnp.einsum("bse,ef->bsf", x, w_gate)
    u = jnp.einsum("bse,ef->bsf", x, w_in)
    u = (jax.nn.silu(g) if cfg.activation == "swiglu" else jax.nn.relu(g)) * u
    return jnp.einsum("bsf,fe->bse", u, w_out)


def _mlp(cfg: GPTConfig, p, router, block_in, mlp_in, stacks=None, layer=None,
         valid=None):
    """The layer's MLP over mlp_in [B, S, E], chosen by `cfg`: dense,
    capacity-routed experts (training) or dropless experts (beside them the
    always-on shared expert of a model that has one). `p` holds the
    layer's weights in cfg.dtype, `router` the router as stored; `stacks`
    are the whole expert stacks where the layer scan did not cut them
    (`_paged_layers`), read at `layer`. Returns (y, aux loss f32 scalar,
    None or the dropless routing's load)."""
    aux, load = jnp.zeros((), jnp.float32), None
    if cfg.mlp_type == "moe" and cfg.moe_routing == "dropless":
        experts = stacks or (p["moe_w_gate"], p["moe_w_in"], p["moe_w_out"])
        y, load = _dropless_mlp(
            cfg, router, experts, mlp_in if cfg.moe_router_in == "mlp" else block_in,
            mlp_in, layer=layer if stacks else None, valid=valid)
        if cfg.moe_shared:
            y = y + _gated_mlp(cfg, mlp_in, p["shared_w_gate"], p["shared_w_in"],
                               p["shared_w_out"])
    elif cfg.mlp_type == "moe":
        from ..ops.moe import moe_forward

        moe_params = {
            "w_router": router,  # router math stays f32
            "w_in": p["moe_w_in"],
            "w_out": p["moe_w_out"],
        }
        if cfg.activation == "swiglu":
            moe_params["w_gate"] = p["moe_w_gate"]
        y, aux = moe_forward(moe_params, mlp_in, cfg.moe_config)
    elif "b_in" in p:
        y = _dense_mlp(cfg, p, mlp_in)
    else:       # a model without biases
        y = _gated_mlp(cfg, mlp_in, p["w_gate"], p["w_in"], p["w_out"])
    return y, aux, load


def _attention_plain(cfg: GPTConfig, q, k, v, positions, window=None):
    """Masked attention in XLA operations for what the kernels do not take:
    K/V heads shared by groups of query heads, and a per-layer window
    (`window`: a traced scalar, query i sees keys j with i - window < j <=
    i). q [B, H, S, Dh]; k, v [B, Hkv, S, Dh]; positions [S]. Latent
    attention's absorbed operands (`_project_latent`) are one K/V head whose
    values (`v` None) are the key row's first `kv_lora_rank` columns."""
    B, H, S, Dh = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, S, Dh)
    scores = jnp.einsum(
        "bgrsd,bgtd->bgrst", qg, k, preferred_element_type=jnp.float32
    )
    if v is None:
        v, scores = k[..., :cfg.kv_lora_rank], scores * _latent_scale(cfg)
    else:
        scores = scores / math.sqrt(Dh)
    i, j = positions[:, None], positions[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out = jnp.einsum("bgrst,bgtd->bgrsd", probs.astype(v.dtype), v)
    return out.reshape(B, H, S, v.shape[-1])


_NO_WINDOW = paged_attention.NO_WINDOW  # a window no sequence reaches: a global layer's


def _layer_kind_xs(cfg: GPTConfig):
    """The per-layer kinds as scan inputs: None, or {"rope" [L] bool,
    "window" [L] int32 (a global layer's is `_NO_WINDOW`)}."""
    kinds = cfg.layer_kinds
    if kinds is None:
        return None
    rope, win = kinds
    return {"rope": jnp.asarray(rope, bool),
            "window": jnp.asarray([w or _NO_WINDOW for w in win], jnp.int32)}


def _refuse_new_fields(cfg: GPTConfig, what: str):
    """A check on input for the programs that cannot take a field: the dense
    cache [L, B, H, M, Dh] has no K/V-head count and no window, and the
    stage split cuts no per-layer kinds and loops over no passes. Grouped-query
    heads, per-layer kinds, a looped stack, latent attention, leading dense
    layers and state-space layers run in `forward` (attn_impl="ref" but for
    the looped stack) and in the paged programs."""
    bad = []
    if cfg.kv_heads != cfg.n_heads:
        bad.append("grouped-query heads (n_kv_heads)")
    if cfg.layer_kinds is not None:
        bad.append("per-layer kinds (rope_layout / sliding_window_layout)")
    if cfg.ut_steps > 1:
        bad.append("layers run several times (ut_steps): no loop of passes, "
                   "and no cache row a (pass, layer) pair")
    if cfg.kv_lora_rank:
        bad.append("latent attention (kv_lora_rank): no cache of one "
                   "compressed row a token")
    if cfg.dense_layers:
        bad.append("leading dense layers (dense_layers) beside the scanned stack")
    if cfg.ssm_layout:
        bad.append("state-space layers (ssm_layout): two stacks of mixers, and "
                   "a state a sequence that no cache here keeps")
    if cfg.n_heads_window:
        bad.append("attention stacks by kind (n_heads_window): one head count "
                   "and one rotary table")
    if cfg.attn_gate: bad.append("a gate a head (attn_gate)")
    if cfg.block_pattern or cfg.layer_pattern or cfg.gdn_interval: bad.append("blocks of one mixer (block_pattern): three stacks by kind" if cfg.block_pattern else "a decoder-hybrid-decoder (layer_pattern): four stacks by a layer's place, rows that later layers read" if cfg.layer_pattern else "gated delta-net layers (gdn_interval): stacks by kind, a state a sequence")
    if bad:
        raise NotImplementedError(f"{what} does not support " + ", ".join(bad))


def _rope_rows(rope_tables, positions):
    """(cos, sin) rows [..., S, rd/2] of the tables at integer `positions`:
    [S] (every lane alike), [] (one position, S = 1) or [B, S] (each lane's
    own), leading dims to broadcast against [B, heads, S, .]."""

    def rows(table):
        r = table[positions]
        if positions.ndim == 2:         # [B, S, rd/2]: the heads' axis goes in
            return r[:, None]
        return r[None] if positions.ndim == 0 else r

    return rows(rope_tables[0]), rows(rope_tables[1])


def _rotary(cfg: GPTConfig, rope_tables, positions, q, k):
    """q and k [B, heads, S, Dh] with their first `rotary_dim` features
    rotated to integer `positions` (`_rope_rows`)."""
    rd = min(cfg.rotary_dim, cfg.d_head)
    c, s = _rope_rows(rope_tables, positions)

    def rotated(x):
        if rd == cfg.d_head:
            return rotate_half(x, c, s)
        return jnp.concatenate([rotate_half(x[..., :rd], c, s), x[..., rd:]], -1)

    return rotated(q), rotated(k)


def _yarn(cfg: GPTConfig, name: str) -> float:
    return dict(cfg.rope_scaling)[name]


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def _latent_scale(cfg: GPTConfig) -> float:
    """What latent attention's scores are multiplied by: one over the root
    of a head's query width (`d_head` + `rotary_dim`), times YaRN's mscale
    (of `mscale_all_dim`) squared where the rotary tables are scaled."""
    scale = 1.0 / math.sqrt(cfg.d_head + cfg.rotary_dim)
    if cfg.rope_scaling and _yarn(cfg, "mscale_all_dim"):
        scale *= _yarn_mscale(_yarn(cfg, "factor"), _yarn(cfg, "mscale_all_dim")) ** 2
    return scale


def _project_latent(cfg: GPTConfig, p, h, rope_tables, positions):
    """Latent attention's ABSORBED operands from the normed input h [B, S,
    E]: (q' [B, H, S, R + Dr], the key row [B, 1, S, R + Dr], expand), R =
    `kv_lora_rank`, Dr = `rotary_dim`.

    A head's score against a key is q_nope . (c W_UK^h) + rot(q_rope) .
    rot(k_r) = (q_nope W_UK^h^T) . c + ...: with the key up-projection moved
    onto the query, every head attends over the SAME row [c | rot(k_r)], the
    row a layer caches, and the weighted sum of the rows' first R columns,
    [B, H, S, R], goes through the head's value up-projection afterwards
    (`expand` -> [B, H, S, d_head]). To `attend` this is one K/V head shared
    by all H query heads, with values that are columns of the keys. The
    published checkpoint interleaves a rotary pair's two features (x[2i],
    x[2i+1]); they are un-interleaved here, on q and k alike, and rotated as
    halves, which leaves every score as it was."""
    R, Dn = cfg.kv_lora_rank, cfg.d_head
    cq = rmsnorm(jnp.einsum("bse,er->bsr", h, p["w_dq"]), p["q_norm_w"])
    q = jnp.einsum("bsr,rhd->bhsd", cq, p["w_uq"])           # [B, H, S, Dn + Dr]
    ckv = jnp.einsum("bse,er->bsr", h, p["w_dkv"])           # [B, S, R + Dr]
    c = rmsnorm(ckv[..., :R], p["kv_norm_w"])
    cos, sin = _rope_rows(rope_tables, positions)

    def rotated(x):
        halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
        return rotate_half(halves, cos, sin)

    w_uk, w_uv = p["w_ukv"][..., :Dn], p["w_ukv"][..., Dn:]   # [R, H, Dn] each
    q = jnp.concatenate([jnp.einsum("bhsd,rhd->bhsr", q[..., :Dn], w_uk),
                         rotated(q[..., Dn:])], -1)
    k = jnp.concatenate([c[:, None], rotated(ckv[:, None, :, R:])], -1)

    def expand(attn):
        return jnp.einsum("bhsr,rhd->bhsd", attn, w_uv)

    return q, k, expand


def _block(cfg: GPTConfig, rope_tables, attend, x, layer_params, positions,
           kind=None, stacks=None, layer=None, valid=None, mixer=None):
    """One transformer block, the only one: x [B, S, E] in cfg.dtype ->
    (x, the state `attend` hands back, MoE aux loss, dropless load or None).

    `attend(q, k, v, kind)` is the program's attention: q [B, H, S, Dh] and
    k, v [B, Hkv, S, Dh] after rotary -> (attention [B, H, S, Dh], whatever
    cache state the program carries); under latent attention the absorbed
    operands of `_project_latent` (one key row a token, `v` None) -> [B, H, S,
    kv_lora_rank]. `kind`: this layer's entry of `_layer_kind_xs` (traced
    scalars) for a model with layers of two kinds; `stacks`, `layer`,
    `valid`: see `_mlp`. `mixer(p, h)`, where the layer's mixer is not
    attention (a state-space layer): the normed input [B, S, E] -> (what the
    mixer adds to the stream [B, S, E], the state it hands back), in
    `attend`'s place. A model without biases lacks their keys. The query
    heads are the weights' own count (`cfg` may be `_window_cfg`'s)."""
    # Cast this layer's master weights to compute dtype (bf16 → MXU).
    p = jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), layer_params)
    block_in = x

    h = _norm(x, p["ln1_w"], p.get("ln1_b"), cfg.norm)
    if mixer is not None:
        attn_out, state = mixer(p, h)
    elif cfg.kv_lora_rank:
        q, k, expand = _project_latent(cfg, p, h, rope_tables, positions)
        attn, state = attend(q, k, None, kind)
        attn = expand(attn)
    else:
        q, k, v = (a.transpose(0, 2, 1, 3) for a in _project_qkv(cfg, p, h))
        # [B, S, heads, Dh] -> [B, heads, S, Dh]
        if cfg.pos == "rotary":
            qr, kr = _rotary(cfg, rope_tables, positions, q, k)
            # A layer without positional encoding keeps q and k as projected.
            q, k = (qr, kr) if kind is None or "rope" not in kind else (
                jnp.where(kind["rope"], qr, q), jnp.where(kind["rope"], kr, k))
        attn, state = attend(q, k, v, kind)
        if "w_head_gate" in p:      # one gate a head a token, from the normed input
            gate = jax.nn.sigmoid(jnp.einsum(      # float32, as the router's logits
                "bse,eh->bhs", h.astype(jnp.float32), p["w_head_gate"].astype(jnp.float32)))
            attn = (attn * gate[..., None]).astype(attn.dtype)
    if mixer is None:
        attn_out = _merge_heads(attn, p["w_o"])
        if "b_o" in p:
            attn_out = attn_out + p["b_o"]
    if cfg.sandwich_norm:
        attn_out = _norm(attn_out, p["ln1_post_w"], p["ln1_post_b"], cfg.norm)

    if cfg.parallel_block:
        mlp_in = h  # GPT-J: same normed input feeds attn and mlp
    else:
        x = x + attn_out
        mlp_in = _norm(x, p["ln2_w"], p.get("ln2_b"), cfg.norm)
    mlp_out, aux, load = _mlp(cfg, p, layer_params.get("moe_router"), block_in,
                              mlp_in, stacks, layer, valid)
    if cfg.sandwich_norm:
        mlp_out = _norm(mlp_out, p["ln2_post_w"], p["ln2_post_b"], cfg.norm)
    out = x + attn_out + mlp_out if cfg.parallel_block else x + mlp_out
    return out, state, aux, load


_LAYER_KEYS = (
    "w_qkv", "w_qkv_served", "b_qkv", "w_q", "w_kv", "w_o", "b_o", "w_head_gate",
    "w_in", "b_in", "w_out", "b_out",
    "ln1_w", "ln1_b", "ln2_w", "ln2_b", "w_gate",
    "moe_router", "moe_w_in", "moe_w_out", "moe_w_gate", *_POST_NORM_KEYS,
    "w_dq", "q_norm_w", "w_uq", "w_dkv", "kv_norm_w", "w_ukv",
    "shared_w_in", "shared_w_gate", "shared_w_out",
)
# A state-space mixer's weights, `ssm_<name>` here for `ops/ssm.py`'s <name>:
# a stack of their own, one entry a state-space layer.
_SSM_KEYS = tuple("ssm_" + name for name in (
    "w_in", "conv_w", "conv_b", "w_x", "dt_norm_w", "b_norm_w", "c_norm_w",
    "w_dt", "b_dt", "A_log", "D", "w_out"))
_ATTN_KEYS = ("w_qkv", "w_qkv_served", "b_qkv", "w_q", "w_kv", "w_o", "b_o", "w_head_gate")
# The window layers' attention where it is a stack of its own (`n_heads_window`).
# {its name in the tree: the name `_block` reads}.
_WINDOW_KEYS = {"win_" + name: name for name in ("w_q", "w_kv", "w_o", "w_head_gate")}


def _embed(params, tokens, positions, cfg: GPTConfig):
    """Token embedding [..., E] in cfg.dtype, plus the learned position rows
    of a model that has them."""
    x = params["tok_embed"][tokens].astype(cfg.dtype)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][positions].astype(cfg.dtype)
    return x


def _rope_tables(cfg: GPTConfig):
    """(cos, sin) [max_seq, rotary_dim/2] float32, or None without rotary.
    Under `rope_scaling` (YaRN) a dimension that turns fewer than
    `beta_slow` times over the original positions is interpolated by
    `factor`, one that turns more than `beta_fast` times is left, a linear
    ramp between; cos and sin carry the group's `attention_factor` where it
    states one, else mscale(`mscale`) / mscale(`mscale_all_dim`). The tables
    of the GLOBAL layers where the window layers have their own (those are
    `_rope_tables(_window_cfg(cfg))`)."""
    if cfg.pos != "rotary":
        return None
    if not cfg.rope_scaling:
        return rope_frequencies(min(cfg.rotary_dim, cfg.d_head), cfg.max_seq,
                                theta=cfg.rope_theta, dtype=jnp.float32)
    d, factor = cfg.rotary_dim, _yarn(cfg, "factor")

    def turns_at(turns):        # the dimension that turns so often
        return d * math.log(_yarn(cfg, "original_max_position_embeddings")
                            / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(turns_at(_yarn(cfg, "beta_fast"))), 0)
    high = min(math.ceil(turns_at(_yarn(cfg, "beta_slow"))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    inv = cfg.rope_theta ** (-np.arange(0, d, 2) / d)
    inv = inv * (1.0 - ramp) + inv / factor * ramp
    amp = dict(cfg.rope_scaling).get("attention_factor") or _yarn_mscale(
        factor, _yarn(cfg, "mscale")) / _yarn_mscale(factor, _yarn(cfg, "mscale_all_dim"))
    ang = jnp.outer(jnp.arange(cfg.max_seq, dtype=jnp.float32),
                    jnp.asarray(inv, jnp.float32))
    return jnp.cos(ang) * amp, jnp.sin(ang) * amp


def _layer_stack(params):
    """The stacked per-layer weights [L, ...]: what the layer scan cuts."""
    return {k: params[k] for k in (*_LAYER_KEYS, *_SSM_KEYS, *_WINDOW_KEYS) if k in params}


def _ssm_weights(p):
    """A layer's state-space mixer weights under `ops/ssm.py`'s names."""
    return {k[4:]: p[k] for k in _SSM_KEYS}


def _pop_expert_stacks(cfg: GPTConfig, layer_stack):
    """None, or an expert model's three stacks (gate, in, out) taken OUT of
    `layer_stack`: a step reads only the experts its tokens chose, so they
    stay whole (a slice a scan cuts would be a copy of every expert of the
    layer) and the layer number finds the expert where it lies."""
    if cfg.mlp_type != "moe":
        return None
    return tuple(layer_stack.pop(k) for k in ("moe_w_gate", "moe_w_in", "moe_w_out"))


def _mixed_layers(layout, layer_stack, carry, lone, run):
    """The layer loop of a model whose layers are of two kinds with weights of
    their own (`ssm_layout`: attention and state-space mixers; `n_heads_window`:
    global and window attention): cut, from the static `layout` (one entry a
    layer of the stack, 0 or 1), into runs of one kind. A run of layers of
    kind 1 is one `lax.scan` over (layer, its index among its kind); a layer
    of kind 0 between two runs is applied as it stands. The stacks stay whole
    and a layer's weights are read where they lie, by index (a slice the scan
    cuts out of a stack would be a copy of it). `lone` and `run` = (the
    kind's own weights as names in the stack, or {name in the stack: name the
    layer reads}, layer): `layer(carry, l, i, p)`
    -> carry, `l` the layer's index in the stack and `i` its index among its
    kind (traced in a scan), `p` its weights under the names `_block` reads:
    the norms and the MLP of layer l beside the kind's own."""
    own = (*lone[0], *run[0])
    shared = {k: v for k, v in layer_stack.items() if k not in own}

    def weights(l, i, names):
        read = names.items() if isinstance(names, dict) else zip(names, names)
        return {**{k: v[l] for k, v in shared.items()},
                **{as_read: layer_stack[k][i] for k, as_read in read if k in layer_stack}}

    l = a = m = 0
    while l < len(layout):
        if not layout[l]:
            carry = lone[1](carry, l, a, weights(l, a, lone[0]))
            l, a = l + 1, a + 1
            continue
        n = next((j for j in range(l, len(layout)) if not layout[j]), len(layout)) - l

        def body(carry, idx):
            return run[1](carry, idx[0], idx[1], weights(idx[0], idx[1], run[0])), None

        carry, _ = jax.lax.scan(
            body, carry, (jnp.arange(l, l + n), jnp.arange(m, m + n)))
        l, m = l + n, m + n
    return carry


def _close_pass(params, x, cfg: GPTConfig):
    """What ends a pass of a looped model: the final norm over the stream
    [..., E] (its output is the next pass's input and, after the last pass,
    what the head reads) and the exit gate on it, one linear unit in
    float32: (x, lambda [...] f32 in (0, 1))."""
    x = _norm(x, params["ln_f_w"], params["ln_f_b"], cfg.norm)
    lam = jax.nn.sigmoid(
        jnp.einsum("...e,e->...", x.astype(jnp.float32),
                   params["exit_gate_w"].astype(jnp.float32))
        + params["exit_gate_b"].astype(jnp.float32))
    return x, lam


def ut_exit_pdf(lams):
    """The exit distribution of a looped model from its gates `lams` [T, ...]
    f32, pass first: p_t = lambda_t * prod_{j<t} (1 - lambda_j) for t < T
    and p_T the remainder -> [T, ...]."""
    stay = jnp.cumprod(1.0 - lams[:-1], axis=0)             # still in after pass t
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lams[:-1] * before, stay[-1:]], axis=0)


def _logits(params, x, cfg: GPTConfig):
    """Final norm and head over hidden states [..., E] -> [..., V] in
    cfg.dtype; the cache programs hand float32 on. A looped model's stream was normed when
    its last pass closed (`_close_pass`), a `block_pattern` model's by its own eps: no second norm."""
    if cfg.ut_steps == 1 and not cfg.block_pattern and not cfg.gdn_interval:     # a `block_pattern` model closes its own
        x = _norm(x, params["ln_f_w"], params.get("ln_f_b"), cfg.norm)
    head = params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...e,ev->...v", x, head.astype(cfg.dtype))


def _layer_loop(cfg: GPTConfig, mesh, positions):
    """The layer scan of the programs that see a whole sequence and keep no
    cache (`forward`, a pipeline stage): (x, layer_stack, kinds=None) ->
    (x, [L] aux). Their `attend` is the configured kernel (`_attention`),
    or `_attention_plain` where heads are grouped or layers have kinds. A
    looped model (`forward` alone) hands `close_pass` too, x -> x: the scan
    then runs `ut_steps` times over the same stack, closed over and not cut
    a pass, each pass ended by `close_pass`; aux is [passes x L]. A model
    with leading dense layers hands their stack as `lead` (`_lead_stack`). A
    model with state-space layers runs `_mixed_layers`, every sequence's
    state starting from zero and dropped at the end; so does a model whose
    window layers have shapes of their own (`by_kind`)."""

    def attend(q, k, v, kind):
        if kind is None and cfg.kv_heads == cfg.n_heads and not cfg.kv_lora_rank:
            return _attention(cfg, q, k, v, mesh), None
        if cfg.attn_impl != "ref":
            raise NotImplementedError(
                "grouped-query heads, per-layer windows and latent attention run "
                f"attn_impl='ref' in forward (got {cfg.attn_impl!r}); the kernels "
                "take none of them")
        window = None if kind is None else kind["window"]
        return _attention_plain(cfg, q, k, v, positions, window), None

    block = functools.partial(_block, cfg, _rope_tables(cfg), attend)
    if cfg.remat:
        block = jax.checkpoint(block, policy=_remat_policy(cfg))

    def scan_body(x, inp):
        layer_params, kind = inp
        x, _, aux, _ = block(x, layer_params, positions, kind=kind)
        return x, aux

    def mixed(x, layer_stack):
        from ..ops import ssm

        B, S, _ = x.shape
        everyone = jnp.ones((B, S), bool)
        tail = jnp.zeros((B, cfg.ssm_conv - 1, cfg.ssm_inner), cfg.dtype)
        s0 = jnp.zeros((B, *ssm.state_shape(cfg.ssm_inner, cfg.ssm_state)), jnp.float32)

        def mixer(p, h):
            return ssm.mamba_mixer(_ssm_weights(p), h, tail, s0, everyone)[0], None

        plain = functools.partial(_block, cfg, None, attend)    # no remat: served
        x = _mixed_layers(
            cfg.ssm_layout, layer_stack, x,
            (_ATTN_KEYS, lambda x, l, a, p: plain(x, p, positions)[0]),
            (_SSM_KEYS, lambda x, l, m, p: plain(x, p, positions, mixer=mixer)[0]))
        return x, jnp.zeros((cfg.n_layers,), jnp.float32)

    def by_kind(x, layer_stack):
        """The stack behind the leading layers of a model whose window layers
        have shapes of their own: each kind through `_block` under its own
        config and rotary table, the experts read where they lie."""
        wcfg = _window_cfg(cfg)
        stacks = _pop_expert_stacks(cfg, layer_stack)
        glob = functools.partial(_block, cfg, _rope_tables(cfg), attend)
        wind = functools.partial(_block, wcfg, _rope_tables(wcfg), attend)
        window = {"window": cfg.sliding_window}
        x = _mixed_layers(
            cfg.sliding_window_layout[cfg.dense_layers:], layer_stack, x,
            (_ATTN_KEYS, lambda x, l, i, p: glob(
                x, p, positions, stacks=stacks, layer=l)[0]),
            (_WINDOW_KEYS, lambda x, l, i, p: wind(
                x, p, positions, kind=window, stacks=stacks, layer=l)[0]))
        return x, jnp.zeros((cfg.n_layers,), jnp.float32)

    def run(x, layer_stack, kinds=None, close_pass=None, lead=None):
        if cfg.ssm_layout:
            return mixed(x, layer_stack)
        if cfg.dense_layers:    # the leading dense layers, the same block
            lead_block = functools.partial(
                _block, _lead_cfg(cfg), _rope_tables(cfg), attend)

            def lead_body(x, layer_params):
                return lead_block(x, layer_params, positions)[0], None

            x, _ = jax.lax.scan(lead_body, x, lead)
        if cfg.n_heads_window:
            return by_kind(x, dict(layer_stack))
        if cfg.ut_steps == 1:
            return jax.lax.scan(scan_body, x, (layer_stack, kinds))

        def one_pass(x, _):
            x, aux = jax.lax.scan(scan_body, x, (layer_stack, kinds))
            return close_pass(x), aux

        x, aux = jax.lax.scan(one_pass, x, None, length=cfg.ut_steps)
        return x, aux.reshape(-1)

    return run


def global_positions(cfg: GPTConfig, local_seq: int):
    """Global token positions for this shard (manual-SPMD mode only). Under
    whole-model shard_map the function body sees only the LOCAL sequence
    chunk — positions must be offset by this device's sp-axis index or
    RoPE/learned-pos phases are wrong on every shard but the first."""
    if cfg.attn_impl in ("ring", "ulysses"):
        offset = jax.lax.axis_index(cfg.sp_axis) * local_seq
        return offset + jnp.arange(local_seq)
    return jnp.arange(local_seq)


def forward(params, tokens, cfg: GPTConfig, positions=None, mesh=None, return_aux=False):
    """tokens [B, S] → logits [B, S, V] (or (logits, moe_aux_loss) with
    return_aux=True).
    mesh=None → plain jit or caller-managed shard_map (manual SPMD).
    mesh given → automatic pjit partitioning with a nested shard_map around
    the attention core when cfg.attn_impl is ring/ulysses.
    """
    if cfg.block_pattern or cfg.layer_pattern or cfg.gdn_interval: return (_pattern_forward if cfg.block_pattern else _sambay_forward if cfg.layer_pattern else _gdn_forward)(params, tokens, cfg, return_aux)
    B, S = tokens.shape
    if positions is None:
        # In automatic (pjit) mode shapes are global — plain arange is right.
        positions = jnp.arange(S) if mesh is not None else global_positions(cfg, S)
    x = _embed(params, tokens, positions, cfg)
    x, aux_stack = _layer_loop(cfg, mesh, positions)(
        x, _layer_stack(params), _layer_kind_xs(cfg),
        lambda x: _close_pass(params, x, cfg)[0], _lead_stack(params))
    logits = _logits(params, x, cfg)
    if return_aux:
        return logits, aux_stack.sum()
    return logits


def _remat_policy(cfg: GPTConfig):
    if cfg.remat_policy not in (None, "dots", "attn"):
        raise ValueError(f"unknown remat_policy: {cfg.remat_policy!r}")
    if cfg.remat_policy == "attn" and cfg.attn_impl != "flash":
        # Only the flash path checkpoint_name's (attn_out, attn_lse);
        # elsewhere save_only_these_names would match nothing and silently
        # rematerialize everything — fail loudly instead.
        raise ValueError(
            "remat_policy='attn' saves flash-attention residuals; it requires "
            f"attn_impl='flash' (got {cfg.attn_impl!r})"
        )
    if cfg.remat_policy == "attn":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse"
        )
    return (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if cfg.remat_policy == "dots"
        else jax.checkpoint_policies.nothing_saveable
    )


def _parse_batch(batch):
    """{"tokens": [B,S+1]} or {"inputs","targets"} → (inputs, targets, mask)."""
    if "inputs" in batch:
        return batch["inputs"], batch["targets"], batch.get("mask")
    tokens = batch["tokens"]
    mask = batch.get("mask")
    return tokens[:, :-1], tokens[:, 1:], (mask[:, 1:] if mask is not None else None)


def _ce_loss(logits, targets, mask):
    """Mean next-token cross-entropy (f32), optionally padding-masked."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        m = mask.astype(jnp.float32)
        return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return -ll.mean()


def _refuse_looped_training(cfg: GPTConfig, what: str):
    """A looped model's published objective is an expected loss over its
    exit distribution with an entropy term; plain next-token cross-entropy
    of the last pass is another objective, so training refuses the model.
    So it refuses what only the serving programs were written for: latent
    attention, leading dense layers, a shared expert, a held range of
    experts (no gradient is exchanged for the absent ones), sigmoid scoring
    (its balance term is not written), state-space layers (the scan's
    backward pass is not written), attention stacks by kind and a gate a head
    (no sharding is written for them)."""
    if cfg.ut_steps > 1:
        raise NotImplementedError(
            f"{what} does not train a model whose layers run several times "
            f"(ut_steps={cfg.ut_steps}): its objective, an expected loss over "
            "the exit distribution, is not implemented")
    served = [name for name, on in (
        ("kv_lora_rank", cfg.kv_lora_rank), ("dense_layers", cfg.dense_layers),
        ("moe_shared", cfg.moe_shared), ("moe_held", cfg.moe_held),
        ("moe_scoring", cfg.moe_scoring != "softmax"),
        ("ssm_layout", cfg.ssm_layout), ("n_heads_window", cfg.n_heads_window),
        ("attn_gate", cfg.attn_gate), ("block_pattern", cfg.block_pattern), ("layer_pattern", cfg.layer_pattern), ("gdn_interval", cfg.gdn_interval)) if on]
    if served:
        raise NotImplementedError(
            f"{what} does not train a model with {', '.join(served)}: "
            "these are served (forward with attn_impl='ref', the paged programs)")


def loss_fn(params, batch, cfg: GPTConfig, mesh=None):
    """batch: {"tokens": [B, S+1]} or {"inputs","targets"} → mean next-token
    cross-entropy (f32) + MoE aux."""
    _refuse_looped_training(cfg, "loss_fn")
    inputs, targets, mask = _parse_batch(batch)
    logits, aux = forward(params, inputs, cfg, mesh=mesh, return_aux=True)
    return _ce_loss(logits, targets, mask) + aux


def make_train_step(cfg: GPTConfig, optimizer, mesh=None, loss=None) -> Callable:
    """Returns `step(state, batch) -> (state, metrics)`; jit at the call site
    with shardings (see ray_tpu.train.JaxTrainer, benchmarks/runners/train.py). `loss`
    overrides the loss callable (params, batch) -> scalar — the pipeline
    train step rides this hook."""
    _refuse_looped_training(cfg, "make_train_step")
    if loss is None:
        def loss(params, batch):
            return loss_fn(params, batch, cfg, mesh)

    def step(state, batch):
        params, opt_state = state
        loss_val, grads = jax.value_and_grad(loss)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u.astype(p.dtype)), params, updates
        )
        gnorm = optax_global_norm(grads)
        return (params, opt_state), {"loss": loss_val, "grad_norm": gnorm}

    return step


def optax_global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


# ------------------------------------------------------- pipeline parallelism
def split_stage_params(params, cfg: GPTConfig, num_stages: int):
    """Reshape the [L, ...] layer stack to [S, L/S, ...] (the `stage` logical
    dim — shard it P('pp') so each pp device holds exactly its stage's
    layers). Non-layer params (embeddings, final norm, head) stay as-is;
    they live outside the pipelined region."""
    if cfg.n_layers % num_stages != 0:
        raise ValueError(f"{cfg.n_layers} layers not divisible by {num_stages} stages")
    per = cfg.n_layers // num_stages
    out = {}
    for k, v in params.items():
        if k in _LAYER_KEYS:
            out[k] = v.reshape(num_stages, per, *v.shape[1:])
        else:
            out[k] = v
    return out


def merge_stage_params(params, cfg: GPTConfig):
    """Inverse of split_stage_params ([S, L/S, ...] -> [L, ...])."""
    out = {}
    for k, v in params.items():
        if k in _LAYER_KEYS:
            out[k] = v.reshape(v.shape[0] * v.shape[1], *v.shape[2:])
        else:
            out[k] = v
    return out


def extract_stage_params(
    params, cfg: GPTConfig, stage: int, num_stages: int,
    num_chunks: int = 1, chunk: int = 0,
):
    """The parameter subset chunk `chunk` of stage `stage` actually needs:
    its layer slice of the S*v virtual-stage split (virtual stage
    vs = chunk*S + stage), plus embeddings on the first virtual stage and
    the final norm + LM head on the last. With num_chunks=1 this is the
    classic per-host weight set for compiled-DAG pipelines; v>1 is the
    interleaved split where each host owns v non-contiguous layer groups
    (in-mesh GPipe keeps the full stacked params instead —
    `split_stage_params`). With tied embeddings, tok_embed lands on BOTH
    boundary virtual stages — the runners reconcile its gradient over the
    embedding bridge before the update."""
    pipeline = num_stages * num_chunks
    if cfg.n_layers % pipeline != 0:
        raise ValueError(
            f"{cfg.n_layers} layers not divisible by {num_stages} stages "
            f"x {num_chunks} chunks"
        )
    if not 0 <= chunk < num_chunks:
        raise ValueError(f"chunk {chunk} out of range for {num_chunks} chunks")
    vs = chunk * num_stages + stage
    per = cfg.n_layers // pipeline
    out = {
        k: v[vs * per : (vs + 1) * per]
        for k, v in params.items()
        if k in _LAYER_KEYS
    }
    first, last = vs == 0, vs == pipeline - 1
    if first or (last and cfg.tie_embeddings):
        out["tok_embed"] = params["tok_embed"]
    if first and cfg.pos == "learned":
        out["pos_embed"] = params["pos_embed"]
    if last:
        out["ln_f_w"] = params["ln_f_w"]
        out["ln_f_b"] = params["ln_f_b"]
        if not cfg.tie_embeddings:
            out["lm_head"] = params["lm_head"]
    return out


def stage_forward(
    stage_params, inp, cfg: GPTConfig, *, first: bool, last: bool,
    positions=None, mesh=None,
):
    """One pipeline stage of `forward`: embed if `first`, this stage's layer
    slice, final norm + head if `last`. `inp` is tokens [B, S] on the first
    stage, activations [B, S, E] (cfg.dtype — what the compiled-DAG edge
    ships between hosts) otherwise. Returns (output, moe_aux_sum)."""
    _refuse_new_fields(cfg, "a pipeline stage")
    S = inp.shape[1]
    if positions is None:
        positions = jnp.arange(S) if mesh is not None else global_positions(cfg, S)
    x = _embed(stage_params, inp, positions, cfg) if first else inp.astype(cfg.dtype)
    x, aux_stack = _layer_loop(cfg, mesh, positions)(x, _layer_stack(stage_params))
    if not last:
        return x, aux_stack.sum()
    return _logits(stage_params, x, cfg), aux_stack.sum()


def check_mpmd_partitionable(
    cfg: GPTConfig, num_stages: int, num_chunks: int = 1
) -> None:
    """Constraints of the MPMD stage split (each stage a SEPARATE jit
    program on its own gang actor — `ray_tpu.train.mpmd`):

    * layers must divide evenly into the S*v virtual stages (same rule as
      in-mesh GPipe for v=1);
    * interleaving (num_chunks > 1) needs num_stages > 1 — chunk-to-chunk
      edges on a single stage would be self-loops;
    * tied embeddings are ALLOWED: tok_embed lives on both boundary
      virtual stages and the runners allreduce its gradient over a
      dedicated first/last-stage bridge channel before the update
      (the Megatron embedding allreduce), keeping the two copies
      bit-identical;
    * MoE is not composed yet: the router aux loss is stage-local and the
      reported loss would silently omit upstream stages' aux terms.
    """
    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    if num_chunks < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    if num_chunks > 1 and num_stages == 1:
        raise ValueError(
            "interleaved MPMD (num_chunks > 1) needs num_stages > 1"
        )
    if cfg.n_layers % (num_stages * num_chunks) != 0:
        raise ValueError(
            f"{cfg.n_layers} layers not divisible by {num_stages} stages "
            f"x {num_chunks} chunks"
        )
    if cfg.mlp_type == "moe":
        raise NotImplementedError(
            "MPMD stages do not carry the MoE aux loss across hosts yet"
        )
    _refuse_new_fields(cfg, "the MPMD stage split")


def make_mpmd_stage_fns(
    cfg: GPTConfig, stage: int, num_stages: int,
    num_chunks: int = 1, chunk: int = 0,
) -> Dict[str, Callable]:
    """Pure per-chunk training functions for the MPMD pipeline (arXiv
    2412.14374 shape: stages as separate jit programs, the host-side 1F1B
    schedule moving activations/grads between them; num_chunks > 1 is the
    interleaved split where this call builds ONE of the stage's v chunk
    programs — virtual stage chunk*S + stage).

    Returned callables (jit them at the call site; all take the chunk's
    param subset from `extract_stage_params`):

    * ``fwd(params, x) -> y`` — forward only. x is tokens [B, S] on the
      first virtual stage, activations [B, S, E] elsewhere; y is the
      activation this chunk ships downstream (logits on the last).
    * non-last chunks: ``fwd_bwd(params, x, gy) -> (param_grads, gx)`` —
      backward via jax.vjp with the forward RECOMPUTED from the saved
      chunk input (activation recomputation: the 1F1B runner stores only
      each in-flight microbatch's chunk INPUT, the memory shape that makes
      deep pipelines fit). On the first virtual stage gx is None (tokens).
    * last chunk: ``loss_bwd(params, x, targets, mask) -> (loss,
      param_grads, gx)`` — next-token CE in f32, grads wrt params and the
      incoming activation.
    """
    check_mpmd_partitionable(cfg, num_stages, num_chunks)
    vs = chunk * num_stages + stage
    first, last = vs == 0, vs == num_stages * num_chunks - 1

    def _fwd(p, x):
        y, _aux = stage_forward(p, x, cfg, first=first, last=last)
        return y

    fns: Dict[str, Callable] = {"fwd": _fwd}
    if last:
        def _loss(p, x, targets, mask):
            logits, _aux = stage_forward(p, x, cfg, first=first, last=True)
            return _ce_loss(logits, targets, mask)

        if first:  # S == 1 degenerate pipeline: input is tokens, no gx
            def loss_bwd(p, x, targets, mask=None):
                loss, gp = jax.value_and_grad(_loss)(p, x, targets, mask)
                return loss, gp, None
        else:
            def loss_bwd(p, x, targets, mask=None):
                loss, (gp, gx) = jax.value_and_grad(_loss, argnums=(0, 1))(
                    p, x, targets, mask
                )
                return loss, gp, gx

        fns["loss_bwd"] = loss_bwd
    else:
        if first:
            def fwd_bwd(p, x, gy):
                # Tokens are integers — differentiate wrt params only.
                _y, vjp = jax.vjp(lambda p_: _fwd(p_, x), p)
                (gp,) = vjp(gy)
                return gp, None
        else:
            def fwd_bwd(p, x, gy):
                _y, vjp = jax.vjp(_fwd, p, x)
                gp, gx = vjp(gy)
                return gp, gx

        fns["fwd_bwd"] = fwd_bwd
    return fns


def pipeline_stage_shardings(cfg: GPTConfig, mesh, rules: Optional[ShardingRules] = None):
    """Param shardings for the stage-split layout: layer arrays gain a
    leading `stage` dim (→ pp); the rest match param_shardings."""
    rules = rules or ShardingRules.default()
    dims = param_logical_dims(cfg)
    out = {}
    for name, d in dims.items():
        if name in _LAYER_KEYS:
            assert d[0] == "layers"
            out[name] = rules.sharding(mesh, "stage", *d)
        else:
            out[name] = rules.sharding(mesh, *d)
    return out


def pipeline_loss_fn(
    params,
    batch,
    cfg: GPTConfig,
    mesh,
    num_microbatches: int,
):
    """GPipe loss: the transformer stack runs inside a shard_map manual over
    ONLY the `pp` axis — microbatches flow stage→stage via ppermute while the
    compiler keeps auto-partitioning each stage's math over dp/fsdp/tp/sp
    (the `axis_names` subset-manual mode). Embedding/head/loss stay outside
    the pipelined region in ordinary pjit land.

    Reference gap being closed: Ray has NO pipeline schedule (SURVEY §2.6 —
    compiled-DAG channels are substrate only); here GPipe's backward emerges
    from jax AD transposing the forward scan. `params` is the stage-split
    layout from split_stage_params.

    Limitations: manual sp attention (ring/ulysses) cannot nest inside the
    pp-manual region — those impls are rejected; with "ref"/"flash" the
    compiler still auto-partitions attention over sp (all-gather based). The
    MoE aux loss is averaged per microbatch (≈ the full-batch value).
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.spmd import shard_fn

    _refuse_new_fields(cfg, "the GPipe pipeline")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"attn_impl={cfg.attn_impl!r} needs its own manual sp axis and "
            "cannot nest inside the pp-manual pipeline region; use 'flash' "
            "or 'ref' (XLA auto-partitions those over sp)."
        )
    S_pp = mesh.shape["pp"]
    M = num_microbatches
    inputs, targets, mask = _parse_batch(batch)
    B, S_len = inputs.shape
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by num_microbatches {M}")
    positions = jnp.arange(S_len)

    x = _embed(params, inputs, positions, cfg)
    # The pipeline input crosses the shard_map boundary in f32: AD transposes
    # its stage-0 broadcast into a psum, and bf16 psums crash the partitioner
    # in subset-manual mode (see the matching forward-path comment below).
    xm = x.reshape(M, B // M, S_len, x.shape[-1]).astype(jnp.float32)

    stage_stack = _layer_stack(params)
    layers = _layer_loop(cfg, None, positions)

    def stage_fn(stage_params, act):
        act, aux_stack = layers(act, stage_params)
        return act, aux_stack.sum()

    def per_stage(stacked, xm):
        local = jax.tree_util.tree_map(lambda p: p[0], stacked)  # my stage
        s = lax.axis_index("pp")
        is_first = s == 0
        is_last = s == S_pp - 1
        fwd_perm = [(i, i + 1) for i in range(S_pp - 1)]
        mb_shape = xm.shape[1:]
        outs0 = jnp.zeros((M,) + mb_shape, cfg.dtype)
        act0 = jnp.zeros(mb_shape, cfg.dtype)

        def tick(carry, t):
            act_in, outs, aux_acc = carry
            x_t = lax.dynamic_index_in_dim(xm, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(is_first, x_t.astype(cfg.dtype), act_in)
            y, aux = stage_fn(local, inp)
            # Stage s holds real data only for ticks s <= t < s + M; bubble
            # ticks chew zeros and must not pollute the MoE aux loss.
            valid = jnp.logical_and(t >= s, t < s + M).astype(jnp.float32)
            aux_acc = aux_acc + aux * valid
            write_idx = jnp.clip(t - (S_pp - 1), 0, M - 1)
            updated = lax.dynamic_update_index_in_dim(outs, y, write_idx, 0)
            outs = jnp.where(jnp.logical_and(is_last, t >= S_pp - 1), updated, outs)
            act_next = lax.ppermute(y, "pp", fwd_perm)
            return (act_next, outs, aux_acc), None

        (_, outs, aux_acc), _ = lax.scan(
            tick, (act0, outs0, jnp.zeros((), jnp.float32)), jnp.arange(M + S_pp - 1)
        )
        # psum in f32: a bf16 psum under subset-manual shard_map crashes the
        # SPMD partitioner ("Invalid binary instruction opcode copy").
        masked = jnp.where(is_last, outs, jnp.zeros_like(outs)).astype(jnp.float32)
        return lax.psum(masked, "pp"), lax.psum(aux_acc, "pp") / M

    gpipe = shard_fn(
        per_stage,
        mesh,
        in_specs=(P("pp"), P()),
        out_specs=(P(), P()),
        manual_axes=frozenset({"pp"}),
    )
    y, aux = gpipe(stage_stack, xm)
    y = y.astype(cfg.dtype).reshape(B, S_len, -1)

    return _ce_loss(_logits(params, y, cfg), targets, mask) + aux


def make_pipeline_train_step(
    cfg: GPTConfig, optimizer, mesh, num_microbatches: int
) -> Callable:
    """`step(state, batch) -> (state, metrics)` with the GPipe pipeline
    inside one jit program (pp × dp/fsdp/tp composition)."""
    return make_train_step(
        cfg,
        optimizer,
        mesh,
        loss=lambda params, batch: pipeline_loss_fn(
            params, batch, cfg, mesh, num_microbatches
        ),
    )


# ---------------------------------------------------------------- generation
# Dense KV-cache generation: prefill fills a [L, B, H, M, Dh] cache in one
# forward, then `lax.scan` advances one token per step on the device, so a
# generation of N tokens is ONE dispatch. Serving runs the paged programs
# below; these are their parity reference (tests/test_generate.py, the
# engine's tests) and chip_smoke.py's check, not a serving path.


def init_cache(cfg: GPTConfig, batch: int, max_seq: Optional[int] = None):
    """Decode cache: stacked per-layer post-RoPE K/V + current length."""
    M = max_seq or cfg.max_seq
    shape = (cfg.n_layers, batch, cfg.n_heads, M, cfg.d_head)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def prefill(params, tokens, cfg: GPTConfig, cache):
    """Run the prompt [B, S0] through the model, filling cache[:, :, :, :S0].

    Returns (last_logits [B, V] f32, cache). Prompts are fixed-length
    (left-pad upstream for ragged batches). No remat (inference)."""
    _refuse_new_fields(cfg, "the dense-cache prefill")
    S = tokens.shape[1]
    positions = jnp.arange(S)
    x = _embed(params, tokens, positions, cfg)
    rope_tables = _rope_tables(cfg)
    icfg = dataclasses.replace(cfg, remat=False, remat_policy=None)

    def attend(q, k, v, kind):          # the post-RoPE K/V are what the cache stores
        return _attention(icfg, q, k, v), (k, v)

    def scan_body(x, layer_params):
        x, kv, _, _ = _block(icfg, rope_tables, attend, x, layer_params, positions)
        return x, kv

    x, (ks, vs) = jax.lax.scan(scan_body, x, _layer_stack(params))  # [L, B, H, S, Dh]
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0)
        ),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0)
        ),
        "len": jnp.asarray(S, jnp.int32),
    }
    return _logits(params, x[:, -1], cfg).astype(jnp.float32), cache


def decode_step(params, token, cache, cfg: GPTConfig):
    """One autoregressive step: token [B] int32 → (logits [B, V] f32, cache).

    Attention is a plain masked dot against the cache — at S=1 the MXU
    matmuls are [B,H,1,D]x[B,H,M,D]; flash brings nothing and Pallas grid
    overhead would dominate."""
    _refuse_new_fields(cfg, "the dense-cache decode_step")
    pos = cache["len"]                       # scalar int32
    x = _embed(params, token[:, None], pos, cfg)            # [B, 1, E]
    rope_tables = _rope_tables(cfg)
    scale = 1.0 / math.sqrt(cfg.d_head)
    cols = jnp.arange(cache["k"].shape[3])

    def attend(ck, cv, q, k, v, kind):
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, pos, 0))
        scores = jnp.einsum(
            "bhsd,bhtd->bhst", q, ck, preferred_element_type=jnp.float32
        ) * scale                                      # [B, H, 1, M]
        scores = jnp.where(cols[None, None, None, :] <= pos, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", probs.astype(cv.dtype), cv), (ck, cv)

    def scan_body(x, inp):
        layer_params, ck, cv = inp
        x, kv, _, _ = _block(cfg, rope_tables, functools.partial(attend, ck, cv),
                             x, layer_params, pos)
        return x, kv

    x, (ks, vs) = jax.lax.scan(
        scan_body, x, (_layer_stack(params), cache["k"], cache["v"]))
    cache = {"k": ks, "v": vs, "len": pos + 1}
    return _logits(params, x[:, -1], cfg).astype(jnp.float32), cache


# --------------------------------------------------- paged KV-cache decode
# Block-table cache layout for the continuous-batching engine
# (`ray_tpu.serve.engine`): the KV cache is a pool of fixed-size token
# blocks [L, NB, BS, H*Dh]; each sequence owns an ordered block table and
# token position p is ONE contiguous row [H*Dh] at (table[p // BS], p % BS)
# (L: the pool's depth in cache layers, `kv_layout`; a looped model's is
# passes x layers).
# Unlike `init_cache`'s dense [L, B, H, M, Dh] layout, sequences of wildly
# different lengths share one physical pool with no per-sequence max_seq
# reservation — the memory model that makes iteration-level admission
# worth doing. Block 0 is the engine's null block: padding lanes in
# bucketed batches point their tables at it so their writes land somewhere
# harmless.
#
# Why rows, and why the pool is the layer scan's CARRY: the TPU keeps an
# array in the layout whose two minor dimensions fill its (16, 128) bf16
# tiles. A pool [.., BS, Dh] with Dh = 64 half-fills a tile, so the device
# stored it block-index-minor and every layer of every paged program
# transposed the layer's whole pool in and out (83% of device time on the
# v5e, PERF.md §6 PR 25). [BS, H*Dh] tiles exactly when H*Dh is a multiple
# of 128, the device keeps it row-major, and a carried, donated pool is
# then updated in place: a program moves only the rows it writes and the
# blocks its lanes read. Other widths stay correct, not fast.
# `scripts/paged_rehearse.py` compiles the three programs for a described
# v5e and lists whatever pool-sized operation is left.


@dataclasses.dataclass(frozen=True)
class KVLayout:
    """What the layers keep between programs, and how they share it. Four
    things are declared here, each once:

    GROUPS: how the layers share the one paged pool [per_group, NB, BS, row].
    A model whose layers are all of one kind is ONE group: per_group = L,
    layer l keeps its rows at pool[l], one block table a sequence. With
    global and window layers the layers are dealt into groups of equal
    size, each of one kind (12 layers = 3 global + 9 window: four groups
    of 3; 52 = 13 + 39: four of 13; 5 = 2 + 3, a leading dense layer among
    the global ones: FIVE groups of one layer, a pool one layer deep and five
    block tables a sequence), so that a block -- `per_group` layers
    x block_size tokens -- has the same bytes whichever group holds it and
    every group draws from the same `num_blocks`. A sequence has one block
    table a group; a window group gives back the blocks that fell behind
    its window while the sequence lives (serve/engine/kv_manager.py). A
    model of one kind with leading dense layers keeps their rows first:
    depth = n_layers, the scanned stack's layer i at pool[dense_layers + i];
    where the layers form groups a leading layer is dealt like any other.

    PASSES: a looped model (`ut_steps` passes over the same layers) keeps
    keys and values a (pass, layer) pair: the pool's leading dimension is
    `depth` = passes x per_group CACHE layers, pass t of layer l at pool[t *
    per_group + slot_of[l]]; block tables, groups and windows are the
    layers' own. Whoever reckons a block's bytes or takes the pool's depth
    takes it from here (`depth`, `block_bytes`), not from `n_layers`.

    ROWS: what a layer keeps a TOKEN, the pool's arrays "k" and "v": a key
    row `key_row` wide and a value row `value_row` wide. A multi-head or
    grouped-query layer keeps kv_heads x d_head of each. A LATENT layer
    (`kv_lora_rank`) keeps ONE row: the normed latent beside the rotated key
    features every head shares, kv_lora_rank + rotary_dim numbers, padded
    with zeros to a whole number of 128-column tiles (576 -> 640), and
    `value_row` is 0: its values are the key row's first kv_lora_rank
    columns, and the pool has no "v". Why 640 in one array, not 576 and not
    two arrays (512 and 64): the compile-only rehearsal for the v5e
    (`scripts/paged_rehearse.py`, PR 34) kept a pool of 576-wide rows
    block-index-minor and copied all of it into and out of that layout in
    every program (what PR 25 removed for K/V rows, which tile exactly when
    their width is a multiple of 128); a second array of 64 would half-fill
    a tile and meet the same copy; rows of 640 stay row-major and are
    updated in place, for a ninth more depth in the score product.

    STATE: what a layer keeps a SEQUENCE, whatever its length. A state-space
    layer (`ssm_layout`) keeps no row: `state_layers` of them keep the
    arrays `state` names, (name, shape a layer a slot, dtype) each, one
    fixed slot a sequence, beside the pool (`init_paged_cache`): the scan's
    float32 state in the shape `ops/ssm.py` keeps it and the convolution's
    last inputs as one row. `slot_of` of such a layer is its index in those arrays; the pool
    is as deep as the layers that DO keep rows (`per_group` counts them alone)."""

    per_group: int                  # layers in a group
    windows: Tuple[int, ...]        # per group: 0 = keeps every token, else the window
    group_of: Tuple[int, ...]       # [L] the layer's group
    slot_of: Tuple[int, ...]        # [L] the layer's index inside a pass's rows
    passes: int = 1                 # rows a layer keeps: one a pass
    key_row: int = 0                # width of the row in pool "k"
    value_row: int = 0              # width of the row in pool "v"; 0: no such pool
    state_layers: int = 0           # layers that keep a state a sequence and no row
    state: Tuple[Tuple[str, Tuple[int, ...], str], ...] = ()
    kinds: Tuple[Tuple[str, int], ...] = (); reads: Tuple[int, ...] = ()    # a `block_pattern` model's blocks: all ("run"), by kind | [L] 1: the layer reads the rows at its group and slot and writes none

    @property
    def depth(self) -> int:
        """Cache layers = the pool's leading dim."""
        return self.passes * self.per_group

    def block_bytes(self, block_size: int, itemsize: int) -> int:
        """Bytes of one block as declared: `depth` cache layers x block_size
        tokens x the rows' widths (what the device pads is not counted)."""
        return self.depth * block_size * (self.key_row + self.value_row) * itemsize

    @property
    def state_bytes(self) -> int:
        """Bytes one sequence's slot holds, over all state layers."""
        return self.state_layers * sum(
            math.prod(shape) * np.dtype(dtype).itemsize for _, shape, dtype in self.state)


@functools.lru_cache(maxsize=None)
def kv_layout(cfg: GPTConfig) -> KVLayout:
    L, kinds = cfg.n_layers, cfg.layer_kinds
    if cfg.block_pattern or cfg.layer_pattern or cfg.gdn_interval: return (_pattern_layout if cfg.block_pattern else _sambay_layout if cfg.layer_pattern else _gdn_layout)(cfg)
    rows = ((-(-(cfg.kv_lora_rank + cfg.rotary_dim) // 128) * 128, 0)
            if cfg.kv_lora_rank else (cfg.kv_heads * cfg.d_head,) * 2)
    if cfg.ssm_layout:      # rows for the attention layers, a slot's state for the rest
        from ..ops import ssm

        kept = [sum(1 for j in cfg.ssm_layout[:l] if j == kind)
                for l, kind in enumerate(cfg.ssm_layout)]
        n_ssm = sum(cfg.ssm_layout)
        return KVLayout(
            L - n_ssm, (0,), (0,) * L, tuple(kept), 1, *rows, n_ssm,
            (("conv", ((cfg.ssm_conv - 1) * cfg.ssm_inner,), jnp.dtype(cfg.dtype).name),
             ("ssm", ssm.state_shape(cfg.ssm_inner, cfg.ssm_state), "float32")))
    win = kinds[1] if kinds is not None else (0,) * L
    glob = [l for l in range(L) if not win[l]]
    wind = [l for l in range(L) if win[l]]
    if not glob or not wind:
        return KVLayout(L, (cfg.sliding_window if wind else 0,), (0,) * L,
                        tuple(range(L)), cfg.ut_steps, *rows)
    per = math.gcd(len(glob), len(wind))
    group_of, slot_of, windows = [0] * L, [0] * L, []
    for layers, w in ((glob, 0), (wind, cfg.sliding_window)):
        for i, l in enumerate(layers):
            group_of[l] = len(windows) + i // per
            slot_of[l] = i % per
        windows += [w] * (len(layers) // per)
    return KVLayout(per, tuple(windows), tuple(group_of), tuple(slot_of),
                    cfg.ut_steps, *rows)


def init_paged_cache(cfg: GPTConfig, num_blocks: int, block_size: int,
                     state_slots: int = 0):
    """Physical paged KV pool: {"k","v"} of [depth, NB, BS, Hkv*Dh] in
    cfg.dtype (`kv_layout`; depth = L for a one-pass model of one kind); for
    a latent model {"k"} alone, [depth, NB, BS, the latent row padded to
    whole tiles]: the rows the layout declares. For a model whose layout
    declares STATE, beside them {"state": {name: [state_layers, 1 +
    state_slots, *shape]}}: `state_slots` sequences' slots behind slot 0, the
    null slot that padding lanes read and write, as block 0 is."""
    lay = kv_layout(cfg)
    shape = (lay.depth, num_blocks, block_size)
    pool = {"k": jnp.zeros(shape + (lay.key_row,), cfg.dtype)}
    if lay.value_row:
        pool["v"] = jnp.zeros(shape + (lay.value_row,), cfg.dtype)
    if lay.state:
        pool["state"] = {
            name: jnp.zeros((lay.state_layers, 1 + state_slots, *dims), dtype)
            for name, dims, dtype in lay.state}
    return pool


def kv_head_rows(cfg: GPTConfig) -> Tuple[int, int, int]:
    """(K/V heads, a head's key row, a head's value row) as attention over
    the paged pool sees them: a latent model's ONE head (`_paged_layers`)."""
    if cfg.kv_lora_rank:
        return 1, kv_layout(cfg).key_row, cfg.kv_lora_rank
    return (cfg.kv_heads // 2, 2 * cfg.d_head, 2 * cfg.d_head) if cfg.layer_pattern else (cfg.kv_heads, cfg.d_head, cfg.d_head)    # differential attention: K/V PAIRS


def attn_heads_by_window(cfg: GPTConfig) -> Tuple[Tuple[int, int], ...]:
    """((window or 0, query heads x passes summed over the attention layers of that window), ...): what the
    host's count of a paged program's attention weighs keys by (`ops/paged_attention.py`; the engine
    works it out once)."""
    if cfg.layer_pattern or cfg.gdn_interval: return _sambay_heads_by_window(cfg) if cfg.layer_pattern else ((0, _gdn_counts(cfg)[1] * cfg.n_heads),)
    win = (cfg.layer_kinds or (None, (0,) * cfg.n_layers))[1]
    heads: Dict[int, int] = {}
    for h, w, ssm in zip(cfg.layer_heads, win, _rowless_layers(cfg)):
        if not ssm:
            heads[w] = heads.get(w, 0) + h * cfg.ut_steps
    return tuple(heads.items())


def _paged_layers(params, tokens, pos, valid, block_tables, kv, cfg: GPTConfig,
                  state_slots=None):
    """Embedding and the layer loop of the three paged programs: lane b
    brings S new tokens, token j at global position pos[b, j], over its
    block table.

    tokens, pos [B, S] int32; valid [B, S] bool (or True) — K/V of invalid
    slots go to the null block, so a padded slot can never clobber a
    neighbouring block through index clamping;
    block_tables [B, W] int32, or [B, G, W] for a model whose layers form G
    groups (`kv_layout`): one table a group, every one W wide, a released
    or not yet allocated entry pointing at the null block. Each layer
    writes the new tokens' K/V rows in place FIRST, then attends causally
    over the table's history (query j sees columns 0..pos[b, j]; on a
    window layer only those above pos[b, j] - window, which is what keeps
    the null block's rows out) — cached prefix, earlier chunks and the new
    tokens themselves all come back through one path. The pool rides the
    scan as its carry, indexed by the layer's slot; the stacked weights
    and the layer's kind (rotary or not, its window, its group) are the
    xs. Attention runs over the keys the step can see, not the table's
    padded width, in the form `ops/paged_attention.py` gives the program's
    shapes (its rule, its four forms, its kernels, the fold of a K/V head's
    query heads): `PagedAttention`, built once here and called once a
    layer. An expert MLP is the dropless layer of `_dropless_mlp`.

    A looped model (`ut_steps` > 1) runs that layer scan `ut_steps` times
    in an outer scan, the pool still the carry and written in place, the
    stacked weights closed over (not cut a pass), pass t of a layer reading
    and writing the pool rows of its own (pass, layer) pair (`kv_layout`),
    every pass closed by the final norm and the exit gate (`_close_pass`).

    A latent model (`kv_lora_rank`) is, to the code below, ONE K/V head
    shared by all H query heads folded into the query axis, whose key row
    (`lay.key_row` wide) also holds its value row as its first
    `kv_lora_rank` columns: `_block` hands the absorbed operands, the pool
    is "k" alone. Leading dense layers run before the scan through the
    same `_block` and `attend`, their rows first in the pool.

    A model with state-space layers (`ssm_layout`) runs `_mixed_layers`: its
    attention layers as above over a pool as deep as they are many, its
    state-space layers through `ops/ssm.py`'s mixer from the state of lane
    b's slot `state_slots[b]` in kv["state"] (gathered, advanced over the
    lane's real tokens, written back in place: the arrays ride the loop's
    carry beside the pool). A lane whose first token sits at position 0
    starts from a ZERO state, whatever its slot held: a new sequence, or a
    preempted one that recomputes. Padding lanes name slot 0.

    Returns (hidden states [B, S, E] before the final norm -- after it for
    a looped model, kv, None or the mean over layers of (experts touched,
    busiest expert's share) [2] f32 ([5] under `moe_held`: `_dropless_mlp`),
    None or a looped model's exit
    distribution [passes run] f32: `ut_exit_pdf` of the gates of the passes
    the pass scan ran, one entry a pass, mean over the real tokens)."""
    moe = cfg.mlp_type == "moe"
    if moe and cfg.moe_routing != "dropless":
        raise NotImplementedError(
            "paged decode serves dropless experts only (moe_routing='dropless'); "
            "capacity-dropping routing is the training path's")
    lay = kv_layout(cfg)
    G = len(lay.windows)
    if G == 1 and block_tables.ndim == 3:
        block_tables = block_tables[:, 0]
    if block_tables.ndim != (2 if G == 1 else 3):
        raise ValueError(
            f"block tables {block_tables.shape} for a model of {G} KV group(s)")
    B, S = tokens.shape
    W = block_tables.shape[-1]
    BS = kv["k"].shape[2]
    Hkv, Dh, Dv = kv_head_rows(cfg)
    scale = _latent_scale(cfg) if cfg.kv_lora_rank else 1.0 / math.sqrt(Dh)
    x = _embed(params, tokens, pos, cfg)               # [B, S, E]
    rope_tables = _rope_tables(cfg)
    blk = jnp.minimum(pos // BS, W - 1)

    def physical(table):                               # [B, W] -> [B, S]
        return jnp.where(valid, jnp.take_along_axis(table, blk, axis=1), 0)

    phys = physical(block_tables) if G == 1 else None
    off = pos % BS
    attention_over = paged_attention.PagedAttention(
        pos, valid, block_tables, BS, Hkv, Dh, Dv, scale, kv["k"].dtype, cfg.n_heads)
    real = attention_over.real      # [B, S]: a real lane's valid slots
    layer_stack = _layer_stack(params)
    kinds = _layer_kind_xs(cfg)
    if kinds is not None:
        kinds["group"] = jnp.asarray(lay.group_of, jnp.int32)
        kinds["slot"] = jnp.asarray(lay.slot_of, jnp.int32)
        if cfg.n_heads_window:      # every layer rotary, each kind by its own table
            del kinds["rope"]

    def attend(kk, vv, l, base, q, k, v, kind):
        """The new rows into the pool (kk, vv) at the layer's slot (past
        `base`: the first row of a looped model's pass, or of the scanned
        stack behind leading dense layers), then attention over the layer's
        table; the pool is the state. A latent pool is `kk` alone, its row
        and the queries padded with zeros to the declared width."""
        if q.shape[-1] < Dh:
            q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, Dh - a.shape[-1]),)) for a in (q, k))
        k = k.transpose(0, 2, 1, 3).reshape(B, S, Hkv * Dh)
        if vv is not None:
            v = v.transpose(0, 2, 1, 3).reshape(B, S, Hkv * Dh)
        if G == 1:
            slot, table, ph = l, block_tables, phys
        else:
            slot = kind["slot"]
            table = jnp.take(block_tables, kind["group"], axis=1)
            ph = physical(table)
        if base is not None:
            slot = base + slot
        kk = kk.at[slot, ph, off].set(k.astype(kk.dtype))
        if vv is not None:
            vv = vv.at[slot, ph, off].set(v.astype(vv.dtype))
        return attention_over(q, kk, vv, slot, table,
                              None if kind is None else kind["window"]), (kk, vv)
    if cfg.gdn_interval: return _gdn_paged(cfg, params, x, kv, attend, real, pos, state_slots, rope_tables)
    if cfg.block_pattern: return _paged_blocks(cfg, params, x, kv, attend, real, pos, state_slots)
    stacks = _pop_expert_stacks(cfg, layer_stack)

    def layers(carry, base=None):
        """The layer scan, once: (x, pool k, pool v) -> the same, [L] loads."""

        def scan_body(carry, inp):
            x, kk, vv = carry                          # kk/vv: the whole pool
            l, layer_params, kind = inp
            x, (kk, vv), _, load = _block(
                cfg, rope_tables, functools.partial(attend, kk, vv, l, base), x,
                layer_params, pos, kind, stacks, l, real)
            return (x, kk, vv), load

        return jax.lax.scan(scan_body, carry, (
            jnp.arange(cfg.n_layers - cfg.dense_layers), layer_stack, kinds))

    def pool(kk, vv):
        return {"k": kk} if vv is None else {"k": kk, "v": vv}

    if cfg.ssm_layout:
        if state_slots is None:
            raise NotImplementedError(
                "a model with state-space layers (ssm_layout) is served by "
                "prefill_paged and decode_step_paged, which name each lane's "
                "state slot; a verify step would have to roll the state back "
                "past the drafts it rejects")
        from ..ops import ssm

        fresh = (pos[:, 0] == 0)[:, None]
        tail_shape = (B, cfg.ssm_conv - 1, cfg.ssm_inner)

        def attn_layer(carry, l, a, p):
            x, kk, vv, st = carry
            x, (kk, vv), _, _ = _block(
                cfg, rope_tables, functools.partial(attend, kk, vv, a, None), x, p,
                pos, valid=real)
            return x, kk, vv, st

        def ssm_layer(carry, l, m, p):
            x, kk, vv, st = carry

            def mixer(p, h):
                tail = jnp.where(fresh, 0, st["conv"][m, state_slots])
                s0 = jnp.where(fresh[..., None, None], 0, st["ssm"][m, state_slots])
                out, tail, s = ssm.mamba_mixer(
                    _ssm_weights(p), h, tail.reshape(tail_shape), s0, real)
                return out, (tail.reshape(B, -1), s)

            x, (tail, s), _, _ = _block(cfg, None, None, x, p, pos, valid=real,
                                        mixer=mixer)
            return x, kk, vv, {"conv": st["conv"].at[m, state_slots].set(tail),
                               "ssm": st["ssm"].at[m, state_slots].set(s)}

        x, kk, vv, st = _mixed_layers(
            cfg.ssm_layout, layer_stack, (x, kv["k"], kv["v"], kv["state"]),
            (_ATTN_KEYS, attn_layer), (_SSM_KEYS, ssm_layer))
        return x, {"k": kk, "v": vv, "state": st}, None, None

    def kind_of(l):
        """Layer l's kind (its window, group and slot) out of `kinds`."""
        return None if kinds is None else {k: v[l] for k, v in kinds.items()}

    carry = (x, kv["k"], kv.get("v"))
    if cfg.dense_layers:    # the leading dense layers: pool rows 0 .. their count
        def lead_body(carry, inp):
            x, kk, vv = carry
            l, layer_params = inp
            x, (kk, vv), _, _ = _block(
                _lead_cfg(cfg), rope_tables, functools.partial(attend, kk, vv, l, None),
                x, layer_params, pos, kind_of(l), valid=real)
            return (x, kk, vv), None

        carry, _ = jax.lax.scan(lead_body, carry, (
            jnp.arange(cfg.dense_layers), _lead_stack(params)))
    if cfg.n_heads_window:
        # Window layers with shapes of their own: each kind through `_block`
        # under its own config and rotary table, a layer's table
        # and pool row by its group and slot, the routing's load summed in
        # the carry (`_mixed_layers` hands nothing else on).
        wcfg, D = _window_cfg(cfg), cfg.dense_layers
        by_kind = ((cfg, rope_tables), (wcfg, _rope_tables(wcfg)))
        attention_over.fold(cfg.n_heads_window)

        def layer_of(kind_cfg, tables):
            def layer(carry, l, i, p):
                x, kk, vv, loads = carry
                x, (kk, vv), _, load = _block(
                    kind_cfg, tables, functools.partial(attend, kk, vv, l, None), x, p,
                    pos, kind_of(D + l), stacks, l, real)
                return x, kk, vv, (loads + load if moe else loads)
            return layer

        loads = jnp.zeros((5 if cfg.moe_held else 2,), jnp.float32) if moe else None
        x, kk, vv, loads = _mixed_layers(
            cfg.sliding_window_layout[D:], layer_stack, (*carry, loads),
            (_ATTN_KEYS, layer_of(*by_kind[0])), (_WINDOW_KEYS, layer_of(*by_kind[1])))
        return x, pool(kk, vv), (loads / (cfg.n_layers - D) if moe else None), None
    if cfg.ut_steps == 1:
        (x, kk, vv), loads = layers(carry, cfg.dense_layers or None)
        return x, pool(kk, vv), (loads.mean(axis=0) if moe else None), None

    def one_pass(carry, t):
        (x, kk, vv), loads = layers(carry, t * lay.per_group)
        x, lam = _close_pass(params, x, cfg)
        return (x, kk, vv), (loads, lam)

    (x, kk, vv), (loads, lams) = jax.lax.scan(
        one_pass, carry, jnp.arange(cfg.ut_steps))
    pdf = jnp.where(real, ut_exit_pdf(lams), 0.0)      # [passes run, B, S]
    exits = pdf.sum(axis=(1, 2)) / jnp.maximum(real.sum(), 1)
    return x, pool(kk, vv), (loads.mean(axis=(0, 1)) if moe else None), exits


def prefill_paged(params, tokens, real_len, pos_offset, block_table, kv,
                  cfg: GPTConfig, state_slot=None):
    """Prompt prefill into the paged cache, one CHUNK of one sequence per
    call (chunked prefill: a long prompt lands a slice per engine step so
    decode streams keep emitting between slices).

    tokens [1, Sp] right-padded to the shape bucket holds
    prompt[pos_offset : pos_offset + real_len]; `real_len` / `pos_offset`
    are traced scalars (one compiled program per (Sp, W) bucket pair covers
    every chunk length and offset); `block_table` [W] int32 maps the
    sequence's blocks ([G, W], one table a group, for a model of G KV
    groups). Prefix-cache hits and earlier chunks' KV below
    `pos_offset` are read from the cache, never recomputed, and a
    monolithic prefill is just the pos_offset=0 chunk covering the whole
    prompt. K/V of padded positions go to the null block. For a model with
    state (`KVLayout.state`) `state_slot` (a traced scalar) names the
    sequence's slot in kv["state"]: the chunk continues the state the chunk
    before it left there (from zero where pos_offset is 0), and its padding
    advances nothing. Returns (next-token logits [V] f32 at global position
    pos_offset + real_len - 1, kv) — only meaningful on the FINAL chunk of a prompt.
    """
    if cfg.layer_pattern: return _sambay_prefill(params, tokens, real_len, pos_offset, block_table, kv, cfg, state_slot)
    rel = jnp.arange(tokens.shape[1])
    pos = (pos_offset + rel)[None]               # global token positions [1, Sp]
    x, kv, _, _ = _paged_layers(
        params, tokens, pos, (rel < real_len)[None], block_table[None], kv, cfg,
        None if state_slot is None else state_slot[None]
    )
    h = x[0, jnp.maximum(real_len - 1, 0)]  # [E] — last REAL chunk position
    return _logits(params, h, cfg).astype(jnp.float32), kv


def decode_step_paged(params, token, positions, block_tables, kv, cfg: GPTConfig,
                      state_slots=None):
    """One iteration-level decode step over the paged cache.

    token [B] int32 — each lane's current token (written at `positions[b]`,
    attending to its own history 0..positions[b]); block_tables [B, W]
    int32. Lanes are independent sequences at unrelated positions — the
    continuous batch. Returns (logits [B, V] f32, kv). Padding lanes
    (block table = null block, position 0) produce garbage logits the
    engine discards. For an expert model (`cfg.mlp_type == "moe"`) the step's
    routing comes back beside the logits: (logits, [2] f32 = experts with
    at least one token and the busiest expert's share of the assignments,
    mean over layers, padding lanes left out), kv. For a looped model
    (`cfg.ut_steps` > 1) what its exit gate read comes back the same way:
    (logits, [passes run] f32 = the exit distribution, one entry a pass the
    program ran, mean over the real lanes), kv. For a model with state, `state_slots` [B] int32 names
    each lane's slot in kv["state"] (a padding lane's is 0): the step is a chunk of one token from that state.
    """
    if cfg.layer_pattern: return _sambay_decode(params, token, positions, block_tables, kv, cfg, state_slots)
    x, kv, load, exits = _paged_layers(
        params, token[:, None], positions[:, None], True, block_tables, kv, cfg,
        state_slots
    )
    logits = _logits(params, x[:, 0], cfg).astype(jnp.float32)
    facts = tuple(a for a in (load, exits) if a is not None)
    return ((logits, *facts), kv) if facts else (logits, kv)


def verify_step_paged(params, tokens, positions, valid_len, block_tables, kv,
                      cfg: GPTConfig):
    """Speculative-decode verify: score k draft tokens (plus the lane's
    current token) in ONE forward over the paged cache.

    tokens [B, K1] int32 — lane b's token j sits at global position
    `positions[b] + j` (j=0 is the last emitted token whose KV has not
    landed yet, j>=1 are draft proposals); `valid_len` [B] int32 is the
    per-lane count of real tokens (<= K1; 0 for padding lanes);
    block_tables [B, W] int32 as in `decode_step_paged`. Each layer
    writes all K1 tokens' K/V first, then attends causally (query j sees
    history 0..positions[b]+j), so logits[b, j] is EXACTLY what a
    sequential `decode_step_paged` would produce after accepting drafts
    0..j-1 — the greedy accept rule (longest matching draft prefix + one
    corrective/bonus token) therefore reproduces non-speculative greedy decode token-for-token. Returns (logits [B, K1, V] f32, kv).
    """
    if cfg.layer_pattern: raise NotImplementedError("a decoder-hybrid-decoder (layer_pattern) takes no verify step: its state is not rolled back past a rejected draft")
    rel = jnp.arange(tokens.shape[1])[None, :]
    pos = positions[:, None] + rel                              # [B, K1]
    x, kv, _, _ = _paged_layers(
        params, tokens, pos, rel < valid_len[:, None], block_tables, kv, cfg
    )
    return _logits(params, x, cfg).astype(jnp.float32), kv


def sample_ids(logits, temperature, key):
    """The one sampler: float32 logits [..., V] -> int32 ids [...]. The
    first of the largest logits at temperature 0 (what NumPy's `argmax`
    gives on the same numbers), a draw from `softmax(logits / temperature)`
    otherwise, every leading index independently under the one `key`. A
    temperature that is a traced scalar (the engine's programs: no program
    is keyed by it) picks the branch on the device."""
    greedy = lambda: jnp.argmax(logits, -1).astype(jnp.int32)
    drawn = lambda: jax.random.categorical(
        key, logits / temperature).astype(jnp.int32)
    if isinstance(temperature, (int, float)):
        return drawn() if temperature > 0.0 else greedy()
    return jax.lax.cond(temperature > 0.0, drawn, greedy)


def make_generate(cfg: GPTConfig, max_new_tokens: int, temperature: float = 0.0):
    """Returns jittable `gen(params, prompt [B, S0], rng) -> tokens
    [B, max_new_tokens]`: prefill + a device-side `lax.scan` decode loop —
    one dispatch per GENERATION, not per token. A reference and a smoke
    check over the dense cache: every prompt of a batch has one length and
    nothing is admitted while it runs (the engine serves the paged
    programs)."""

    def gen(params, prompt, rng):
        B, S0 = prompt.shape
        cache = init_cache(cfg, B, S0 + max_new_tokens)
        logits, cache = prefill(params, prompt, cfg, cache)
        rng, k0 = jax.random.split(rng)
        first = sample_ids(logits, temperature, k0)

        def step(carry, key):
            token, cache = carry
            logits, cache = decode_step(params, token, cache, cfg)
            nxt = sample_ids(logits, temperature, key)
            return (nxt, cache), token

        keys = jax.random.split(rng, max_new_tokens - 1) if max_new_tokens > 1 \
            else jnp.zeros((0, 2), jnp.uint32)
        (last, _), toks = jax.lax.scan(step, (first, cache), keys)
        return jnp.concatenate([toks.T, last[:, None]], axis=1)

    return gen


# ------------------------------------------------- the stack an engine holds
def _project_served(p, h):
    """`_project_qkv` from the form an engine holds (`hold_served`): the three
    matrices stacked, each OUT-features (heads, Dh) by IN-features,
    w_qkv_served [3, H, Dh, E], in ONE product. The one form that the decode
    programs at every lane count, the prefill chunk and the verify step all
    read as it lies: from the public [E, 3, H, Dh], which the device tiles
    over (H, Dh), each of them first rewrote the whole stack, every call
    (1.15 GiB for `ouro-2.6b`). Heads stay NAMED in the product: said flat
    ("bse,tfe->btsf", then a reshape) the chunk program pays three copies of
    its activations a layer instead (PERF.md §6, PR 50, with the forms that
    did NOT cure it)."""
    qkv = jnp.einsum("bse,thde->btshd", h, p["w_qkv_served"]) + p["b_qkv"][:, None]
    return qkv[:, 0], qkv[:, 1], qkv[:, 2]


@jax.jit
def _served_qkv(w_qkv):
    return w_qkv.transpose(0, 2, 3, 4, 1)       # [L, E, 3, H, Dh] -> [L, 3, H, Dh, E]


def hold_served(params):
    """The tree as an engine holds it on the device, for the paged programs
    alone: w_qkv [L, E, 3, H, Dh] re-formed ONCE to w_qkv_served [L, 3, H, Dh,
    E] (`_project_served`), under a key of its own so that the tree says
    which form it is in; every other leaf, the bias too, as it came. The
    caller's tree is not touched. A tree without w_qkv (the grouped-query
    pair, latent attention) comes back itself. Returns (tree, the bytes held
    in another form than the caller's). `init_params`, the trainer, `forward`,
    checkpoints and the Hugging Face bridge keep the public form."""
    if "w_qkv" not in params:
        return params, 0
    held = {k: v for k, v in params.items() if k != "w_qkv"}
    w = held["w_qkv_served"] = _served_qkv(params["w_qkv"])
    return held, w.size * w.dtype.itemsize


# ------------------------------------------ blocks of ONE mixer (`block_pattern`)
# A model whose block l is ONE mixer under ONE norm, h <- h + mixer_c(RMSNorm_l(h)),
# its kind c the l-th character of `cfg.block_pattern` (the `nemotron_h` family's
# `hybrid_override_pattern`): "M" a Mamba-2 mixer (`ops/ssm.py` `mamba2_mixer`:
# `ssm_heads` heads of `ssm_head_dim` channels over a state of `ssm_state`, B and C
# shared by `ssm_groups` groups, `ssm_conv` taps over x, B and C together), "E"
# dropless experts of TWO matrices (`activation` "relu2", `ops/moe.py`; sigmoid
# scores, a selection bias, `moe_shared` always-on experts of `d_mlp` stored as one
# matrix pair, a range held under `moe_held`), "*" grouped-query attention without a
# positional term. No block has a second sublayer, so no block runs `_block`. The
# weights are THREE stacks by kind, each with its own norm (`m2_*` [M blocks, ...],
# `moe_*` (`moe_w_in` [E blocks, held, F, E]: out-features first) / `shared_*`, `attn_ln_w` / `w_q` / `w_kv` / `w_o` [*
# blocks, ...]) and the pattern is static: a block reads its stack at its index among
# its kind, where it lies. An M block keeps a state a sequence (`KVLayout.state`: the
# convolution's tail and the float32 state [heads, head_dim, state]), a * block rows
# a token, an E block nothing. Served by `forward` and the paged programs on one
# chip; everything else refuses it by name. This section stands at the END of the
# file, and its callers above were edited without moving a line (ROADMAP D20).
_BLOCK_KINDS = {"M": "ssm", "E": "moe", "*": "attn"}
_M2_KEYS = ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm_w", "w_out")


def _check_block_pattern(cfg: GPTConfig):
    """`GPTConfig.__post_init__`'s checks of the fields this section reads."""
    if cfg.moe_select_bias and cfg.moe_scoring != "sigmoid":
        raise ValueError("moe_select_bias: a selection bias goes with moe_scoring='sigmoid'")
    if cfg.block_pattern is None:
        if cfg.activation == "relu2":
            raise ValueError('activation "relu2" is the two-matrix expert of a '
                             "block_pattern model; no other MLP here is written without a gate")
        return
    if len(cfg.block_pattern) != cfg.n_layers or set(cfg.block_pattern) - set(_BLOCK_KINDS):
        raise ValueError(f"block_pattern {cfg.block_pattern!r}: one of M, E, * for each "
                         f"of the {cfg.n_layers} blocks")
    if (cfg.norm != "rmsnorm" or cfg.pos != "none" or cfg.activation != "relu2"
            or cfg.mlp_type != "moe" or cfg.moe_routing != "dropless"
            or cfg.moe_scoring != "sigmoid" or cfg.tie_embeddings or cfg.init != "unit_stream"
            or cfg.ssm_layout or cfg.layer_kinds is not None or cfg.ut_steps > 1
            or cfg.kv_lora_rank or cfg.dense_layers or cfg.sandwich_norm or cfg.parallel_block
            or cfg.n_heads_window or cfg.attn_gate or cfg.kv_heads == cfg.n_heads
            or cfg.ssm_heads < 1 or cfg.ssm_head_dim < 1 or cfg.ssm_heads % cfg.ssm_groups):
        raise ValueError(
            "block_pattern: a one-pass RMSNorm model without positional term or bias, an "
            'untied head, init="unit_stream", grouped-query attention blocks, Mamba-2 blocks '
            "(ssm_heads x ssm_head_dim, ssm_groups dividing the heads) and dropless "
            'sigmoid-scored experts of two matrices (mlp_type="moe", activation="relu2")')


def _pattern_counts(cfg: GPTConfig) -> Dict[str, int]:
    """{"M", "E", "*"}: blocks of each kind."""
    return {c: cfg.block_pattern.count(c) for c in _BLOCK_KINDS}


def _moe_layers(cfg: GPTConfig) -> int:
    """Layers (blocks) whose MLP is the routed experts."""
    if cfg.mlp_type != "moe":
        return 0
    return _pattern_counts(cfg)["E"] if cfg.block_pattern else cfg.n_layers - cfg.dense_layers


# A property of the config, attached here because the class body's lines are counted (D20).
GPTConfig.moe_layers = property(_moe_layers)


def _m2_conv_width(cfg: GPTConfig) -> int:
    """Channels the Mamba-2 convolution runs over: x, B and C together."""
    return cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state


def _pattern_params(cfg: GPTConfig) -> int:
    """`GPTConfig.n_params` of a `block_pattern` model: what the tree holds."""
    E, F, V, n = cfg.d_model, cfg.d_mlp, cfg.vocab_size, _pattern_counts(cfg)
    Di, Dc, H = cfg.ssm_heads * cfg.ssm_head_dim, _m2_conv_width(cfg), cfg.ssm_heads
    mamba = E * (Di + Dc + H) + Dc * cfg.ssm_conv + Dc + 3 * H + Di + Di * E + E
    experts = ((cfg.held_experts + cfg.moe_shared) * 2 * E * F + E * cfg.moe_experts
               + cfg.moe_select_bias * cfg.moe_experts + E)
    attn = 2 * E * cfg.d_head * (cfg.n_heads + cfg.kv_heads) + E
    return n["M"] * mamba + n["E"] * experts + n["*"] * attn + 2 * V * E + E


def _init_pattern(rng, cfg: GPTConfig) -> Dict[str, jnp.ndarray]:
    """`init_params` of a `block_pattern` model under init="unit_stream": a matrix of
    fan-in n has std gain / sqrt(n), its gain from the preset's `init_gains`; norms
    1; the Mamba-2 steps log-uniform in [1e-3, 1e-1] through `dt_bias` (softplus
    inverted), A = -(1 .. 16) a head, D = 1 (the published initialisation's ranges);
    the selection bias seeded NON-ZERO (std `select_bias`), so that leaving it out
    changes which experts serve a token."""
    E, F, V, X, n = cfg.d_model, cfg.d_mlp, cfg.vocab_size, cfg.held_experts, _pattern_counts(cfg)
    Lm, Le, La = n["M"], n["E"], n["*"]
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    Hs, Di, Dc, K = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim, _m2_conv_width(cfg), cfg.ssm_conv
    k = jax.random.split(rng, 16)
    dt, g = cfg.param_dtype, dict(cfg.init_gains)

    def normal(key, shape, gain, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain / math.sqrt(fan_in))).astype(dt)

    step = jnp.exp(jax.random.uniform(k[12], (Lm, Hs), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    params = {
        "tok_embed": normal(k[0], (V, E), g["embed"], 1),
        "lm_head": normal(k[1], (E, V), g["head"], E),
        "ln_f_w": jnp.ones((E,), dt),
        "m2_ln_w": jnp.ones((Lm, E), dt),
        "m2_w_in": normal(k[2], (Lm, E, Di + Dc + Hs), g["ssm_in"], E),
        "m2_conv_w": normal(k[3], (Lm, K, Dc), g["ssm_conv"], K),
        "m2_conv_b": jnp.zeros((Lm, Dc), dt),
        "m2_dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "m2_A_log": jnp.log(jax.random.uniform(k[13], (Lm, Hs), jnp.float32, 1.0, 16.0)).astype(dt),
        "m2_D": jnp.ones((Lm, Hs), dt),
        "m2_norm_w": jnp.ones((Lm, Di), dt),
        "m2_w_out": normal(k[4], (Lm, Di, E), g["ssm_out"], Di),
        "moe_ln_w": jnp.ones((Le, E), dt),
        "moe_router": normal(k[5], (Le, E, cfg.moe_experts), g["router"], E),
        "moe_w_in": normal(k[6], (Le, X, F, E), g["mlp_in"], E),    # out-features first (ops/moe.py)
        "moe_w_out": normal(k[7], (Le, X, F, E), g["expert_out"], F),
        "attn_ln_w": jnp.ones((La, E), dt),
        "w_q": normal(k[8], (La, E, H, Dh), g["q"], E),
        "w_kv": jnp.stack([normal(k[9], (La, E, Hkv, Dh), g["k"], E),
                           normal(k[10], (La, E, Hkv, Dh), g["v"], E)], axis=2),
        "w_o": normal(k[11], (La, H, Dh, E), g["o"], H * Dh),
    }
    if cfg.moe_select_bias:
        params["moe_select_bias"] = normal(k[14], (Le, cfg.moe_experts), g["select_bias"], 1)
    if cfg.moe_shared:
        ks, Fs = jax.random.split(k[15]), cfg.moe_shared * F
        params["shared_w_in"] = normal(ks[0], (Le, E, Fs), g["mlp_in"], E)
        params["shared_w_out"] = normal(ks[1], (Le, Fs, E), g["mlp_out"], Fs)
    return params


def _rowless_layers(cfg: GPTConfig) -> Tuple[int, ...]:
    """[L] 1 where the layer keeps no K/V row a token (a state-space layer, or a
    `block_pattern` block that is not attention)."""
    if cfg.block_pattern:
        return tuple(int(c != "*") for c in cfg.block_pattern)
    return tuple(int(c in "mg") for c in cfg.layer_pattern) if cfg.layer_pattern else cfg.ssm_layout or (0,) * cfg.n_layers


def _pattern_layout(cfg: GPTConfig) -> KVLayout:
    """`kv_layout` of a `block_pattern` model: ONE group as deep as the attention
    blocks, a state a sequence for the Mamba-2 blocks (the convolution's last
    inputs as one row in the compute dtype; the float32 state [heads, head_dim,
    state]), `slot_of` a block's index among its kind, and the blocks a program runs: all
    of them ("run") and by kind, what the engine's books count a dispatch."""
    from ..ops import ssm

    seen, slot_of = dict.fromkeys(_BLOCK_KINDS, 0), []
    for c in cfg.block_pattern:
        slot_of.append(seen[c])
        seen[c] += 1
    row = cfg.kv_heads * cfg.d_head
    return KVLayout(
        seen["*"], (0,), (0,) * cfg.n_layers, tuple(slot_of), 1, row, row, seen["M"],
        (("conv", ((cfg.ssm_conv - 1) * _m2_conv_width(cfg),), jnp.dtype(cfg.dtype).name),
         ("ssm", ssm.mamba2_state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
          "float32")),
        (("run", cfg.n_layers), *((_BLOCK_KINDS[c], n) for c, n in seen.items())))


def _relu2_mlp(x, w_in, w_out):
    """W_out relu(W_in x)^2: the always-on expert of two matrices."""
    u = jnp.einsum("bse,ef->bsf", x, w_in)
    return jnp.einsum("bsf,fe->bse", jnp.square(jax.nn.relu(u)), w_out)


def _pattern_blocks(cfg: GPTConfig, params, x, valid, mamba, attention):
    """The block loop of a `block_pattern` model over x [B, S, E]: each block's norm,
    its mixer, the sum into the stream. `mamba(i, p, h)` and `attention(i, q, k, v)`
    are the program's own (where the state and the rows come from and go to): an M
    block's weights `p` under `ops/ssm.py`'s names and the normed stream -> what the
    mixer adds; q [B, H, S, Dh], k, v [B, Hkv, S, Dh] -> attention [B, H, S, Dh];
    `i` the block's index among its kind. The expert blocks are the same in every
    program. Returns (x under the FINAL norm, whose eps `_logits` does not take: that
    one leaves such a stream alone; the routing's load summed over the expert blocks)."""
    dt = cfg.dtype
    norm = lambda w: rmsnorm(x, w.astype(dt), cfg.norm_eps)
    index = dict.fromkeys(_BLOCK_KINDS, 0)
    loads = jnp.zeros((5 if cfg.moe_held else 2,), jnp.float32)
    for c in cfg.block_pattern:
        i = index[c]
        index[c] += 1
        if c == "M":
            h = norm(params["m2_ln_w"][i])
            x = x + mamba(i, {name: params["m2_" + name][i].astype(dt) for name in _M2_KEYS}, h)
        elif c == "E":
            h = norm(params["moe_ln_w"][i])
            y, load = _dropless_mlp(
                cfg, params["moe_router"][i], (None, params["moe_w_in"], params["moe_w_out"]),
                h, h, layer=i, valid=valid,
                bias=params["moe_select_bias"][i] if cfg.moe_select_bias else None)
            if cfg.moe_shared:
                y = y + _relu2_mlp(h, params["shared_w_in"][i].astype(dt),
                                   params["shared_w_out"][i].astype(dt))
            x, loads = x + y, loads + load
        else:
            h = norm(params["attn_ln_w"][i])
            q, k, v = (a.transpose(0, 2, 1, 3) for a in _project_qkv(
                cfg, {"w_q": params["w_q"][i].astype(dt), "w_kv": params["w_kv"][i].astype(dt)}, h))
            x = x + _merge_heads(attention(i, q, k, v), params["w_o"][i].astype(dt))
    return rmsnorm(x, params["ln_f_w"].astype(dt), cfg.norm_eps), loads


def _paged_blocks(cfg: GPTConfig, params, x, kv, attend, real, pos, state_slots):
    """`_paged_layers` for a `block_pattern` model, from its embedding x [B, S, E] on:
    the attention blocks through `_paged_layers`' own `attend` over a pool as deep as
    they are many, the Mamba-2 blocks from the state of lane b's slot `state_slots[b]`
    in kv["state"] (gathered, advanced over the lane's real tokens, written back in
    place), a lane whose first token sits at position 0 from a ZERO state, as a
    state-space layer of `ssm_layout` does. Returns what `_paged_layers` returns."""
    if state_slots is None:
        raise NotImplementedError(
            "a model with Mamba-2 blocks (block_pattern) is served by prefill_paged "
            "and decode_step_paged, which name each lane's state slot; a verify step "
            "would have to roll the state back past the drafts it rejects")
    from ..ops import ssm

    B = x.shape[0]
    fresh = (pos[:, 0] == 0)[:, None]
    tail_shape = (B, cfg.ssm_conv - 1, _m2_conv_width(cfg))
    pool = {"kv": (kv["k"], kv["v"]), "state": kv["state"]}

    def mamba(i, p, h):
        st = pool["state"]
        tail = jnp.where(fresh, 0, st["conv"][i, state_slots])
        s0 = jnp.where(fresh[..., None, None], 0, st["ssm"][i, state_slots])
        out, tail, s = ssm.mamba2_mixer(
            p, h, tail.reshape(tail_shape), s0, real, groups=cfg.ssm_groups,
            chunk=cfg.ssm_chunk, eps=cfg.norm_eps)
        pool["state"] = {"conv": st["conv"].at[i, state_slots].set(tail.reshape(B, -1)),
                         "ssm": st["ssm"].at[i, state_slots].set(s)}
        return out

    def attention(i, q, k, v):
        attn, pool["kv"] = attend(*pool["kv"], i, None, q, k, v, None)
        return attn

    x, loads = _pattern_blocks(cfg, params, x, real, mamba, attention)
    kk, vv = pool["kv"]
    return x, {"k": kk, "v": vv, "state": pool["state"]}, loads / cfg.moe_layers, None


def _pattern_forward(params, tokens, cfg: GPTConfig, return_aux: bool):
    """`forward` of a `block_pattern` model: the whole sequence, every sequence's
    state from zero and dropped at the end, plain causal attention."""
    from ..ops import ssm

    B, S = tokens.shape
    x = _embed(params, tokens, None, cfg)
    everyone = jnp.ones((B, S), bool)
    tail = jnp.zeros((B, cfg.ssm_conv - 1, _m2_conv_width(cfg)), cfg.dtype)
    s0 = jnp.zeros((B, *ssm.mamba2_state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)),
                   jnp.float32)
    x, _ = _pattern_blocks(
        cfg, params, x, everyone,
        lambda i, p, h: ssm.mamba2_mixer(p, h, tail, s0, everyone, groups=cfg.ssm_groups,
                                         chunk=cfg.ssm_chunk, eps=cfg.norm_eps)[0],
        lambda i, q, k, v: _attention_plain(cfg, q, k, v, jnp.arange(S)))
    logits = _logits(params, x, cfg)
    return (logits, jnp.zeros((), jnp.float32)) if return_aux else logits


def nemotron3_nano_30b_a3b(**kw):
    """NVIDIA-Nemotron-3-Nano-30B-A3B (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-
    A3B-BF16, `model_type: "nemotron_h"`): 52 blocks of 2688, each ONE mixer under one
    RMSNorm (eps 1e-5) by `hybrid_override_pattern`: 23 Mamba-2 (64 heads of 64 over a
    state of 128, 8 groups, 4 taps, chunks of 128), 23 expert blocks (128 routed experts
    of 1856, W_down relu(W_up x)^2, top-6 by sigmoid score + selection bias, weights
    normalised x 2.5, one shared expert of 3712 = `moe_shared` 2 x 1856 in one matrix
    pair) and 6 attention blocks (32 query heads over 2 K/V heads of 128, no positional
    term); vocabulary 131,072, untied head; no bias but the convolution's. Serving only:
    `forward` and the paged programs. The benchmark runs stage 0 of four (13 blocks), 64
    of the 128 experts held, half the vocabulary (benchmarks/configs)."""
    pattern = kw.get("block_pattern", "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    return GPTConfig(
        **{
            **dict(
                n_layers=len(pattern),
                block_pattern=pattern,
                d_model=2688,
                n_heads=32,
                n_kv_heads=2,
                d_head=128,
                d_mlp=1856,
                vocab_size=131072,
                max_seq=262144,
                norm="rmsnorm",
                norm_eps=1e-5,
                activation="relu2",
                pos="none",
                tie_embeddings=False,
                ssm_heads=64,
                ssm_head_dim=64,
                ssm_groups=8,
                ssm_state=128,
                ssm_conv=4,
                ssm_chunk=128,
                mlp_type="moe",
                moe_routing="dropless",
                moe_experts=128,
                moe_top_k=6,
                moe_scoring="sigmoid",
                moe_route_scale=2.5,
                moe_router_in="mlp",
                moe_select_bias=True,
                moe_shared=2,
                param_dtype=jnp.bfloat16,
                # Random weights under which a rounding grows 5-fold through 13 blocks
                # (`embed` 2.5: each block adds 0.3-1 to a stream of 2.5; at 1.0 it grew
                # 55-fold and a sound engine read as float8 weights do) and a routed
                # expert swapped at a near-tie moves a logit less than float8 does
                # (`expert_out` 0.15), with every other mechanism a visible part of the
                # logits (`scripts/nemotron_h_tolerance.py` reads each on the chip;
                # PERF.md §6, PR 51). No program's shape or time depends on the numbers.
                init="unit_stream",
                init_gains=(("embed", 2.5), ("head", 1.0), ("q", 1.2), ("k", 1.2), ("v", 1.0),
                            ("o", 1.0), ("router", 2.0), ("select_bias", 0.3),
                            ("mlp_in", 1.0), ("mlp_out", 0.5), ("expert_out", 0.15),
                            ("ssm_in", 1.0), ("ssm_conv", 1.0), ("ssm_out", 1.0)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


CONFIGS["nemotron3-nano-30b-a3b"] = nemotron3_nano_30b_a3b


# ------------------------- a decoder-hybrid-decoder (`layer_pattern`, SambaY)
# A model of two decoders over one stream (arXiv:2507.06607; Phi-4-mini-flash-
# reasoning): every layer l is a = h + mixer_l(LN1_l(h)), h <- a + MLP_l(LN2_l(a))
# under LayerNorms WITH bias and the dense SiLU-gated MLP, its mixer the l-th
# character of `cfg.layer_pattern`:
#
#   "m" Mamba-1 as published (no inner norm: `ops/sambay.py` `mamba_mixer_plain`),
#       a state a sequence; the LAST one also hands out its scan output `m`
#   "w" differential attention under `sliding_window`, rows in a window group
#   "f" differential attention, full and causal, rows in the ONE full group
#   "g" a gated memory unit over `m` of the same token: keeps nothing
#   "c" differential CROSS attention: its own queries over the "f" layer's rows,
#       through that layer's block table; it writes no row and keeps nothing
#
# in the form (mw)+ m f (gc)+: a SELF-decoder of (m, w) pairs closed by the (m, f)
# pair, and a CROSS-decoder of (g, c) pairs that has no state of its own and mixes
# tokens only through the "f" layer's rows, so its output at a position needs
# nothing of its output at earlier positions: a prefill chunk runs it on ONE token
# a lane, the last real one (`_sambay_paged`), and the positions before a prompt's
# last have no logits. Differential attention rides the paged kernels as
# grouped-query attention over K/V PAIRS of 2 x d_head = 128 (`ops/sambay.py`,
# `kv_head_rows`). No positional term. The weights are stacks by a layer's place in
# its pair: `sm_*`, `sa_*` [self pairs, ...] (the Mamba layer and the attention
# layer of a self-decoder pair, each with its own norms and MLP) and `cg_*`, `ca_*`
# [cross pairs, ...]; the walk is TWO `lax.scan`s, one a decoder, the window and the
# layer's index riding as data. Served by `forward` and the paged programs on one
# chip; everything else refuses it by name. At the END of the file, its callers
# above edited without moving a line (ROADMAP D20).
_SAMBAY_MAMBA = ("w_in", "conv_w", "conv_b", "w_x", "w_dt", "b_dt", "A_log", "D", "w_out")
_SAMBAY_GATHER_TOKENS = 128     # tokens of a gathered slice of the pool (`_sambay_paged`)


def _sambay_pairs(cfg: GPTConfig) -> Tuple[int, int]:
    """(self-decoder pairs, the (m, f) pair among them; cross-decoder pairs)."""
    cross = cfg.layer_pattern.count("c")
    return cfg.n_layers // 2 - cross, cross


def _check_layer_pattern(cfg: GPTConfig):
    """`GPTConfig.__post_init__`'s checks of the fields this section reads."""
    if cfg.layer_pattern is None:
        return
    n, pattern = cfg.n_layers, cfg.layer_pattern
    P = pattern.count("m")
    if len(pattern) != n or pattern != "mw" * (P - 1) + "mf" + "gc" * (n // 2 - P) \
            or P < 2 or P == n // 2:
        raise ValueError(f"layer_pattern {pattern!r}: (mw)+ m f (gc)+ over the "
                         f"{n} layers")
    if (cfg.norm != "layernorm" or cfg.pos != "none" or cfg.activation != "swiglu"
            or cfg.mlp_type != "dense" or not cfg.tie_embeddings or cfg.init != "unit_stream"
            or cfg.block_pattern or cfg.ssm_layout or cfg.layer_kinds is not None
            or cfg.ut_steps > 1 or cfg.kv_lora_rank or cfg.dense_layers or cfg.sandwich_norm
            or cfg.parallel_block or cfg.n_heads_window or cfg.attn_gate
            or cfg.n_heads % 2 or cfg.kv_heads % 2 or cfg.n_heads % cfg.kv_heads
            or cfg.sliding_window < 1):
        raise ValueError(
            "layer_pattern: a one-pass LayerNorm model without positional term, a tied "
            'head, init="unit_stream", the dense SiLU-gated MLP, an even number of query '
            "and of K/V heads (differential attention pairs them) and sliding_window >= 1")


def _sambay_params(cfg: GPTConfig) -> int:
    """`GPTConfig.n_params` of a `layer_pattern` model: what the tree holds."""
    E, F, V, Hd, Kd = cfg.d_model, cfg.d_mlp, cfg.vocab_size, cfg.n_heads * cfg.d_head, cfg.kv_heads * cfg.d_head
    Di, N, R, K = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    P, C = _sambay_pairs(cfg)
    layer = 4 * E + 3 * E * F
    mamba = E * 2 * Di + Di * K + Di + Di * (R + 2 * N) + R * Di + Di + N * Di + Di + Di * E
    lam = 4 * cfg.d_head + 2 * cfg.d_head
    attn = E * (Hd + 2 * Kd) + Hd + 2 * Kd + Hd * E + E + lam
    cross = E * Hd + Hd + Hd * E + E + lam
    return (cfg.n_layers * layer + P * (mamba + attn) + C * (2 * E * Di + cross)
            + V * E + 2 * E)


def _init_sambay(rng, cfg: GPTConfig) -> Dict[str, jnp.ndarray]:
    """`init_params` of a `layer_pattern` model under init="unit_stream": a matrix of
    fan-in n has std gain / sqrt(n), its gain from the preset's `init_gains`; the
    Mamba mixers by the family's published initialisation (`_init_ssm_stack`'s: steps
    log-uniform in 0.001-0.1 through `b_dt`, A = -(1 .. N), D = 1), the B and C columns
    of `w_x` under a gain of their own (`ssm_bc`: no inner norm scales them here);
    EVERY bias and the `lambda` vectors seeded non-zero (`bias`, `lam`), so that
    leaving one out changes the result; the final norm's gain alternating in sign, as
    every tied preset's (`_init_unit_stream`)."""
    E, F, V, H, Hkv, Dh = cfg.d_model, cfg.d_mlp, cfg.vocab_size, cfg.n_heads, cfg.kv_heads, cfg.d_head
    Di, N, R, K = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    (P, C), dt, g = _sambay_pairs(cfg), cfg.param_dtype, dict(cfg.init_gains)
    keys = iter(jax.random.split(rng, 64))

    def n(shape, gain, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain / math.sqrt(fan_in))).astype(dt)

    def layer(L):       # the norms and the MLP every layer has
        return {"ln1_w": jnp.ones((L, E), dt), "ln1_b": n((L, E), g["bias"], 1),
                "ln2_w": jnp.ones((L, E), dt), "ln2_b": n((L, E), g["bias"], 1),
                "w_gate": n((L, E, F), g["mlp_in"], E), "w_in": n((L, E, F), g["mlp_in"], E),
                "w_out": n((L, F, E), g["mlp_out"], F)}

    def attention(L, cross):        # the projection's columns: q, or q | k | v
        heads = ((H, "q"),) if cross else ((H, "q"), (Hkv, "k"), (Hkv, "v"))
        w = jnp.concatenate([n((L, E, h * Dh), g[name], E) for h, name in heads], axis=-1)
        return {"w_q" if cross else "w_qkv": w,
                "b_q" if cross else "b_qkv": n((L, w.shape[-1]), g["bias"], 1),
                "w_o": n((L, H * Dh, E), g["o"], H * Dh), "b_o": n((L, E), g["bias"], 1),
                "lam": n((L, 4, Dh), g["lam"], 1), "sub_w": jnp.ones((L, 2 * Dh), dt)}

    step = jnp.exp(jax.random.uniform(next(keys), (P, Di), jnp.float32)
                   * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    mamba = {
        "w_in": n((P, E, 2 * Di), g["ssm_in"], E), "conv_w": n((P, K, Di), g["ssm_conv"], K),
        "conv_b": n((P, Di), g["bias"], 1),
        "w_x": jnp.concatenate([n((P, Di, R), g["ssm_x"], Di),
                                n((P, Di, 2 * N), g["ssm_bc"], Di)], axis=-1),
        "w_dt": n((P, R, Di), g["ssm_dt"], R),
        "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
                                  (P, N, Di)).astype(dt),
        "D": jnp.ones((P, Di), dt), "w_out": n((P, Di, E), g["ssm_out"], Di)}
    stacks = {
        "sm": {**layer(P), **{"ssm_" + name: a for name, a in mamba.items()}},
        "sa": {**layer(P), **attention(P, False)},
        "cg": {**layer(C), "gmu_gate": n((C, E, Di), g["gmu_gate"], E),
               "gmu_out": n((C, Di, E), g["gmu_out"], Di)},
        "ca": {**layer(C), **attention(C, True)}}
    return {"tok_embed": n((V, E), g["embed"], 1),
            "ln_f_w": jnp.where(jnp.arange(E) % 2, -1, 1).astype(dt),
            "ln_f_b": n((E,), g["bias"], 1),
            **{f"{role}_{name}": a for role, stack in stacks.items() for name, a in stack.items()}}


def _sambay_layout(cfg: GPTConfig) -> KVLayout:
    """`kv_layout` of a `layer_pattern` model: every "w" layer a window group of its
    own and the "f" layer the one full group (per_group 1: a pool ONE layer deep, a
    block table a group a sequence); a "c" layer on the "f" layer's group and slot,
    marked in `reads` (it writes no row there); an "m" layer's slot its index among
    the state arrays; a "g" layer keeps nothing (group 0, slot 0: never read)."""
    from ..ops import ssm

    P, _ = _sambay_pairs(cfg)
    group_of = [l // 2 if c in "wf" else P - 1 if c == "c" else 0
                for l, c in enumerate(cfg.layer_pattern)]
    slot_of = [l // 2 if c == "m" else 0 for l, c in enumerate(cfg.layer_pattern)]
    row = cfg.kv_heads * cfg.d_head
    return KVLayout(
        1, (cfg.sliding_window,) * (P - 1) + (0,), tuple(group_of), tuple(slot_of), 1,
        row, row, P,
        (("conv", ((cfg.ssm_conv - 1) * cfg.ssm_inner,), jnp.dtype(cfg.dtype).name),
         ("ssm", ssm.state_shape(cfg.ssm_inner, cfg.ssm_state), "float32")),
        reads=tuple(int(c == "c") for c in cfg.layer_pattern))


def _sambay_heads_by_window(cfg: GPTConfig) -> Tuple[Tuple[int, int], ...]:
    """`attn_heads_by_window`: the "w" layers under the window, the "f" layer and the
    "c" layers that read its rows under none."""
    n = cfg.layer_pattern.count
    return ((cfg.sliding_window, n("w") * cfg.n_heads), (0, (n("f") + n("c")) * cfg.n_heads))


def _sambay_stack(params, role: str, i):
    """Layer i of the stack `role` (`sm`, `sa`, `cg`, `ca`), read where it lies."""
    return {name[3:]: a[i] for name, a in params.items() if name.startswith(role + "_")}


def _sambay_layer(cfg: GPTConfig, p, x, mixer):
    """One layer over x [B, S, E]: `mixer(normed stream) -> (what it adds to the
    stream, what else it hands on)`. Returns (x, what the mixer handed on)."""
    dt = cfg.dtype
    norm = lambda x, w, b: layernorm(x, w.astype(dt), b.astype(dt), cfg.norm_eps)
    out, handed = mixer(norm(x, p["ln1_w"], p["ln1_b"]))
    a = x + out
    return a + _gated_mlp(cfg, norm(a, p["ln2_w"], p["ln2_b"]), p["w_gate"].astype(dt),
                          p["w_in"].astype(dt), p["w_out"].astype(dt)), handed


def _sambay_attention(cfg: GPTConfig, p, h, layer, attend):
    """A differential attention mixer over h [B, S, E]: `attend(q [B, H, S, 2 Dh], k,
    v [B, S, Hkv Dh] or None for a cross layer) -> ([B, H, S, 2 Dh], store)`, `layer`
    its index in the whole stack (a traced scalar). Returns (what it adds, store)."""
    from ..ops import sambay

    dt, (B, S, _), Hd = cfg.dtype, h.shape, cfg.n_heads * cfg.d_head
    cross = "w_q" in p
    qkv = (jnp.einsum("bse,ef->bsf", h, p["w_q" if cross else "w_qkv"].astype(dt))
           + p["b_q" if cross else "b_qkv"].astype(dt))
    k, v = (None, None) if cross else jnp.split(qkv[..., Hd:], 2, axis=-1)
    attn, store = attend(
        sambay.diff_queries(qkv[..., :Hd].reshape(B, S, cfg.n_heads, cfg.d_head)), k, v)
    out = sambay.diff_combine(attn, sambay.diff_lambda(p["lam"], layer), p["sub_w"],
                              1.0 - sambay.lambda_init(layer), cfg.norm_eps)
    return jnp.einsum("bsf,fe->bse", out, p["w_o"].astype(dt)) + p["b_o"].astype(dt), store


def _sambay_decoders(cfg: GPTConfig, params, x, store, mamba, attend, last):
    """Both decoders over x [B, S, E]. `store` is whatever `mamba` and `attend` keep
    between layers (the pool and the state arrays, or a dense layer's rows); it rides
    both scans' carry. `mamba(store, i, p, h) -> (out, (m, store))`: pair i's Mamba
    mixer, `p` its weights under `ops/ssm.py`'s names; `attend(store, i, window, q, k,
    v) -> (attn, store)`: pair i's attention under `window` (an int32 scalar), a cross
    layer's where k is None (i then P - 1: the "f" layer's rows). `last(x, m) -> (x,
    m)` cuts the stream and `m` to the tokens the cross-decoder runs on, between the
    decoders. Returns (x, store)."""
    from ..ops import sambay

    dt, (P, C) = cfg.dtype, _sambay_pairs(cfg)
    windows = jnp.asarray([cfg.sliding_window] * (P - 1) + [_NO_WINDOW], jnp.int32)

    def self_pair(carry, inp):
        x, _, store = carry
        i, window = inp
        pm, pa = _sambay_stack(params, "sm", i), _sambay_stack(params, "sa", i)
        weights = {name: pm["ssm_" + name].astype(dt) for name in _SAMBAY_MAMBA}
        x, (m, store) = _sambay_layer(cfg, pm, x, lambda h: mamba(store, i, weights, h))
        x, store = _sambay_layer(cfg, pa, x, lambda h: _sambay_attention(
            cfg, pa, h, 2 * i + 1, functools.partial(attend, store, i, window)))
        return (x, m, store), None

    m0 = jnp.zeros(x.shape[:2] + (cfg.ssm_inner,), dt)
    (x, m, store), _ = jax.lax.scan(self_pair, (x, m0, store), (jnp.arange(P), windows))
    x, m = last(x, m)

    def cross_pair(carry, i):
        x, store = carry
        pg, pc = _sambay_stack(params, "cg", i), _sambay_stack(params, "ca", i)
        x, _ = _sambay_layer(cfg, pg, x, lambda h: (sambay.gated_memory_unit(
            h, m, pg["gmu_gate"].astype(dt), pg["gmu_out"].astype(dt)), None))
        x, store = _sambay_layer(cfg, pc, x, lambda h: _sambay_attention(
            cfg, pc, h, 2 * (P + i) + 1,
            lambda q, k, v: attend(store, P - 1, windows[P - 1], q, None, None)))
        return (x, store), None

    (x, store), _ = jax.lax.scan(cross_pair, (x, store), jnp.arange(C))
    return x, store


def sambay_cross_tokens(tokens: int, chunk: bool) -> int:
    """How many of a program's `tokens` tokens a lane the cross-decoder of a
    `layer_pattern` model runs on: ONE in a chunk program (the cross layers mix tokens
    only through the "f" layer's rows, so only the token whose logits are read needs
    them), all of them otherwise (a decode step's one). `_sambay_paged` shapes the
    cross-decoder's stream by this answer and the engine books it for every chunk
    program it dispatches (`engine.py` `_book_shared`): one rule, asked twice."""
    return 1 if chunk else tokens


def _sambay_paged(params, tokens, pos, valid, block_tables, kv, cfg: GPTConfig,
                  state_slots, last=None):
    """`_paged_layers` for a `layer_pattern` model: lane b brings S new tokens at
    positions pos[b] over its NINE block tables [B, G, W] (`_sambay_layout`) and its
    state slot. The self-decoder runs over all S: a "w" / "f" layer writes its rows
    into its own group's blocks FIRST and attends over that table (`PagedAttention`,
    the pool one layer deep: slot 0), an "m" layer advances the state of the lane's
    slot over its real tokens (from ZERO where the lane's first token sits at
    position 0, as `_paged_layers` has it). The cross-decoder runs on ONE token a
    lane: `last` [B] names it among the S (a chunk's last real token), None where S
    is 1 (a decode step); its "c" layers attend from that token's position over the
    "f" layer's table in the one-query form and write nothing. (`sambay_cross_tokens`
    is that "ONE": the stream is cut to as many tokens as it says, those that end at
    `last`.) Returns (the stream of those tokens [B, 1, E] before the final norm, kv)."""
    from ..ops import sambay

    if state_slots is None:
        raise NotImplementedError(
            "a decoder-hybrid-decoder (layer_pattern) is served by prefill_paged and "
            "decode_step_paged, which name each lane's state slot")
    (B, S), W, BS = tokens.shape, block_tables.shape[-1], kv["k"].shape[2]
    Hkv, Dh, Dv = kv_head_rows(cfg)
    shape = (BS, Hkv, Dh, Dv, cfg.d_head ** -0.5, kv["k"].dtype, cfg.n_heads)
    x = _embed(params, tokens, pos, cfg)
    blk, off = jnp.minimum(pos // BS, W - 1), pos % BS
    # A program of several tokens a lane GATHERS its table's rows (`PagedAttention`'s
    # one-shot and chunk forms), and the chip's compiler cuts a gather whose slices
    # pass some 512 KiB (a block of 512 tokens of 1280 is 1.25 MiB) into column
    # strips of the WHOLE pool first: 8 ms a layer a GiB of pool (PERF.md §6, PR 56).
    # Such a program reads the pool as blocks of at most `_SAMBAY_GATHER_TOKENS`, a
    # reshape that moves nothing, each table entry as that many entries: the same
    # keys, tiles and bounds, so the host's count stands as it is. The split belongs
    # where the gather lives, `ops/paged_attention.py` `PagedAttention._tiled`, which
    # D20 keeps shut in a PR that adds a cell: the PR that opens that file (ROADMAP
    # S2, S17) takes it there for every model and deletes it here.
    r = BS // _SAMBAY_GATHER_TOKENS if S > 1 and BS % _SAMBAY_GATHER_TOKENS == 0 else 1
    fine = jnp.where(block_tables[..., None] == 0, 0,
                     block_tables[..., None] * r + jnp.arange(r)).reshape(B, -1, W * r)
    over = paged_attention.PagedAttention(pos, valid, fine, BS // r, *shape[1:])
    real = over.real
    fresh = (pos[:, 0] == 0)[:, None]
    tail_shape = (B, cfg.ssm_conv - 1, cfg.ssm_inner)
    # (attention, its tables, blocks it reads a pool block as) of the self-decoder's
    # layers and of the cross layers: one query a lane, the pool as it lies
    n = sambay_cross_tokens(S, last is not None)
    at = None if last is None else last[:, None] + jnp.arange(1 - n, 1)    # n, ending at `last`
    forms = {False: (over, fine, r), True: (over, fine, r) if last is None else (
        paged_attention.PagedAttention(
            jnp.take_along_axis(pos, at, axis=1), jnp.take_along_axis(real, at, axis=1),
            block_tables, *shape), block_tables, 1)}

    def mamba(store, i, p, h):
        kk, vv, conv, state = store
        tail = jnp.where(fresh, 0, conv[i, state_slots])
        s0 = jnp.where(fresh[..., None, None], 0, state[i, state_slots])
        out, m, tail, s = sambay.mamba_mixer_plain(p, h, tail.reshape(tail_shape), s0, real)
        return out, (m, (kk, vv, conv.at[i, state_slots].set(tail.reshape(B, -1)),
                         state.at[i, state_slots].set(s)))

    def attend(store, i, window, q, k, v):
        kk, vv, conv, state = store
        table = jnp.take(block_tables, i, axis=1)
        if k is not None:       # a "w" or "f" layer: its rows first
            ph = jnp.where(valid, jnp.take_along_axis(table, blk, axis=1), 0)
            kk = kk.at[0, ph, off].set(k.astype(kk.dtype))
            vv = vv.at[0, ph, off].set(v.astype(vv.dtype))
        attention, tables, blocks = forms[k is None]
        rows = lambda pool: pool.reshape(1, -1, BS // blocks, pool.shape[-1])
        return (attention(q, rows(kk), rows(vv), 0, jnp.take(tables, i, axis=1), window),
                (kk, vv, conv, state))

    def at_last(x, m):
        if last is None:
            return x, m
        return (jnp.take_along_axis(x, at[..., None], axis=1),
                jnp.take_along_axis(m, at[..., None], axis=1))

    st = kv["state"]
    x, (kk, vv, conv, state) = _sambay_decoders(
        cfg, params, x, (kv["k"], kv["v"], st["conv"], st["ssm"]), mamba, attend, at_last)
    return x, {"k": kk, "v": vv, "state": {"conv": conv, "ssm": state}}


def _sambay_prefill(params, tokens, real_len, pos_offset, block_table, kv, cfg, state_slot):
    """`prefill_paged` of a `layer_pattern` model: the self-decoder over the chunk, the
    cross-decoder on its last real token alone."""
    rel = jnp.arange(tokens.shape[1])
    x, kv = _sambay_paged(
        params, tokens, (pos_offset + rel)[None], (rel < real_len)[None], block_table[None],
        kv, cfg, None if state_slot is None else state_slot[None],
        jnp.maximum(real_len - 1, 0)[None])
    return _logits(params, x[0, -1], cfg).astype(jnp.float32), kv


def _sambay_decode(params, token, positions, block_tables, kv, cfg, state_slots):
    """`decode_step_paged` of a `layer_pattern` model: all the layers on one token a lane."""
    x, kv = _sambay_paged(
        params, token[:, None], positions[:, None], True, block_tables, kv, cfg, state_slots)
    return _logits(params, x[:, 0], cfg).astype(jnp.float32), kv


def _sambay_forward(params, tokens, cfg: GPTConfig, return_aux: bool):
    """`forward` of a `layer_pattern` model: the whole sequence through every layer at
    every position, every sequence's state from zero and dropped at the end, the
    differential attention's two softmaxes dense under the layer's mask."""
    from ..ops import sambay, ssm

    B, S = tokens.shape
    x = _embed(params, tokens, None, cfg)
    everyone = jnp.ones((B, S), bool)
    tail = jnp.zeros((B, cfg.ssm_conv - 1, cfg.ssm_inner), cfg.dtype)
    s0 = jnp.zeros((B, *ssm.state_shape(cfg.ssm_inner, cfg.ssm_state)), jnp.float32)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    rows = lambda a: a.reshape(B, S, cfg.kv_heads // 2, 2 * cfg.d_head).transpose(0, 2, 1, 3)

    def mamba(store, l, p, h):
        out, m, _, _ = sambay.mamba_mixer_plain(p, h, tail, s0, everyone)
        return out, (m, store)

    def attend(store, l, window, q, k, v):
        if k is not None:       # a cross layer (k None) reads what the "f" layer left
            store = (rows(k), rows(v))
        k, v = store
        qg = q.reshape(B, k.shape[1], -1, S, q.shape[-1])
        scores = jnp.einsum("bgrsd,bgtd->bgrst", qg, k,
                            preferred_element_type=jnp.float32) * cfg.d_head ** -0.5
        probs = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), scores, -1e30), axis=-1)
        out = jnp.einsum("bgrst,bgtd->bgrsd", probs.astype(v.dtype), v)
        return out.reshape(q.shape[:3] + v.shape[-1:]), store

    zero = jnp.zeros((B, cfg.kv_heads // 2, S, 2 * cfg.d_head), cfg.dtype)
    x, _ = _sambay_decoders(cfg, params, x, (zero, zero), mamba, attend, lambda x, m: (x, m))
    logits = _logits(params, x, cfg)
    return (logits, jnp.zeros((), jnp.float32)) if return_aux else logits


def phi4_mini_flash(**kw):
    """Phi-4-mini-flash-reasoning (huggingface.co/microsoft/Phi-4-mini-flash-reasoning,
    `model_type: "phi4flash"`, the SambaY decoder-hybrid-decoder of arXiv:2507.06607): 32
    layers of 2560 under LayerNorms with bias (eps 1e-5) and a SiLU-gated MLP of 10,240
    each; even layers below 18 Mamba-1 (inner width 5120, state 16, 4 taps, rank 160,
    no inner norm), odd ones below 16 differential attention under a window of 512,
    layer 17 full differential attention (40 query heads over 20 K/V heads of 64,
    paired); layers 18-30 even gated memory units over layer 16's scan output, 19-31 odd
    differential cross attention over layer 17's rows; vocabulary 200,064 TIED; no
    positional term. Serving only: `forward` and the paged programs, whole on one chip."""
    L = kw.get("n_layers", 32)
    return GPTConfig(
        **{
            **dict(
                n_layers=L,
                layer_pattern="mw" * (L // 4) + "mf" + "gc" * (L // 4 - 1),
                d_model=2560,
                n_heads=40,
                n_kv_heads=20,
                d_head=64,
                d_mlp=10240,
                vocab_size=200064,
                max_seq=262144,
                norm="layernorm",
                norm_eps=1e-5,
                activation="swiglu",
                pos="none",
                sliding_window=512,
                ssm_state=16,
                ssm_conv=4,
                ssm_expand=2,
                ssm_dt_rank=160,
                tie_embeddings=True,
                param_dtype=jnp.bfloat16,
                # As `jamba2_3b`: a stream that keeps the token (embedding std 1) under
                # layers that together add about as much again. No inner norm scales B
                # and C here, so their columns of `w_x` carry the gain that Jamba's
                # norms did (`ssm_bc`: B and C of size 1.5-2), which makes what the
                # state adds the larger part of `m` beside the skip term D c; the
                # memory units' `gmu_out` and the mixers' `ssm_out` keep a rounding from
                # growing through 32 layers (`scripts/phi4flash_tolerance.py` reads the
                # growth and each control on the chip; PERF.md §6, PR 56). Biases and
                # the `lambda` vectors are seeded non-zero (`bias`, `lam`: N(0, 0.1)) so
                # that leaving one out is seen. No program's shape or time depends on
                # the numbers.
                init="unit_stream",
                init_gains=(("embed", 1.0), ("q", 1.2), ("k", 1.2), ("v", 1.0), ("o", 1.0),
                            ("mlp_in", 1.0), ("mlp_out", 0.35), ("bias", 0.1), ("lam", 0.1),
                            ("ssm_in", 1.0), ("ssm_conv", 1.0), ("ssm_x", 1.0),
                            ("ssm_bc", 3.0), ("ssm_dt", 1.0), ("ssm_out", 0.2),
                            ("gmu_gate", 1.0), ("gmu_out", 0.2)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


CONFIGS["phi4-mini-flash"] = phi4_mini_flash


# ------------------------------ gated delta-net layers (`gdn_interval`, Qwen3-Next)
# A pre-norm residual stack under ZERO-CENTRED RMSNorms (x / rms(x) * (1 + w), `w`
# stored: the block norms, the final one and the norms a query and a key head) whose
# layer l is x <- x + Mixer_l(N1(x)); x <- x + MoE(N2(x)). The mixer is GATED
# ATTENTION where (l + 1) % `gdn_interval` == 0: `n_heads` query heads over
# `n_kv_heads` K/V heads of `d_head`, the query projection twice as wide (head n = [q_n
# | gate_n]), a norm a query and a key head before the rotary term over the first
# `rotary_dim` features, sigmoid(gate) times the attention output ELEMENTWISE before
# the output projection. Every other layer is a GATED DELTA NET (`ops/delta.py`):
# `ssm_heads` value heads of `ssm_head_dim` served by `ssm_groups` key heads of
# `ssm_state`, `ssm_conv` taps over q, k and v together, chunks of `ssm_chunk`; its
# state a sequence (`KVLayout.state`: the convolution's tail and the float32 matrix
# [heads, key, value]). The MLP of EVERY layer is the dropless experts (softmax over
# the kept logits, a range held under `moe_held`) beside ONE shared expert times
# sigmoid(w . h), a scalar a token. The weights are stacks by kind: `gdn_*` [delta
# layers, ...], `ga_*` [attention layers, ...], the norms and the MLP [L, ...]; the
# walk is a `lax.scan` over the periods of `gdn_interval` layers, inside it a scan over
# the period's delta layers, each layer's weights read where they lie. Served by
# `forward` and the paged programs on one chip; everything else refuses it by name.
# At the END of the file, its callers above edited without moving a line (ROADMAP D20).
_GDN_KEYS = ("w_qkvz", "w_ba", "conv_w", "dt_bias", "A_log", "norm_w", "w_out")


def _check_gdn(cfg: GPTConfig):
    """`GPTConfig.__post_init__`'s checks of the fields this section reads."""
    if not cfg.gdn_interval:
        return
    if (cfg.gdn_interval < 2 or cfg.n_layers % cfg.gdn_interval or cfg.norm != "rmsnorm"
            or cfg.pos != "rotary" or cfg.activation != "swiglu" or cfg.mlp_type != "moe"
            or cfg.moe_routing != "dropless" or cfg.moe_scoring != "softmax"
            or cfg.moe_router_in != "mlp" or cfg.moe_shared != 1 or cfg.tie_embeddings
            or cfg.init != "unit_stream" or cfg.block_pattern or cfg.layer_pattern
            or cfg.ssm_layout or cfg.layer_kinds is not None or cfg.ut_steps > 1
            or cfg.kv_lora_rank or cfg.dense_layers or cfg.sandwich_norm or cfg.parallel_block
            or cfg.n_heads_window or cfg.attn_gate or cfg.rope_scaling
            or cfg.ssm_heads < 1 or cfg.ssm_head_dim < 1 or cfg.ssm_heads % cfg.ssm_groups):
        raise ValueError(
            "gdn_interval: periods of gdn_interval >= 2 layers dividing n_layers in a "
            'one-pass RMSNorm model with an unscaled rotary term, an untied head, '
            'init="unit_stream", gated delta-net layers (ssm_heads x ssm_head_dim over '
            "ssm_groups key heads of ssm_state) and, in every layer, dropless softmax-"
            "routed gated experts on the normed MLP input beside one shared expert")


def _gdn_counts(cfg: GPTConfig) -> Tuple[int, int]:
    """(gated delta-net layers, gated attention layers)."""
    periods = cfg.n_layers // cfg.gdn_interval
    return cfg.n_layers - periods, periods


def _gdn_conv_width(cfg: GPTConfig) -> int:
    """Channels under the delta net's convolution: q, k and v together."""
    return 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads * cfg.ssm_head_dim


def _gdn_params(cfg: GPTConfig) -> int:
    """`GPTConfig.n_params` of a `gdn_interval` model: what the tree holds."""
    E, F, V, Hs = cfg.d_model, cfg.d_mlp, cfg.vocab_size, cfg.ssm_heads
    Dv, Dc, Hd = Hs * cfg.ssm_head_dim, _gdn_conv_width(cfg), cfg.n_heads * cfg.d_head
    delta = (E * (Dc + Dv) + E * 2 * Hs + Dc * cfg.ssm_conv + 2 * Hs + cfg.ssm_head_dim
             + Dv * E)
    attn = E * 2 * Hd + 2 * E * cfg.kv_heads * cfg.d_head + Hd * E + 2 * cfg.d_head
    mlp = (cfg.held_experts + 1) * 3 * E * F + E * cfg.moe_experts + E
    n_delta, n_attn = _gdn_counts(cfg)
    return (n_delta * delta + n_attn * attn + cfg.n_layers * (mlp + 2 * E)
            + 2 * V * E + E)


def _init_gdn(rng, cfg: GPTConfig) -> Dict[str, jnp.ndarray]:
    """`init_params` of a `gdn_interval` model under init="unit_stream": a matrix of
    fan-in n has std gain / sqrt(n), its gain from the preset's `init_gains`; the
    zero-centred norms' stored gains N(0, `norm`) (published: 0; seeded so that a
    plain gain reads otherwise), the delta net's inner gain 1; its steps log-uniform
    in 0.001-0.1 through `dt_bias` (softplus inverted) and A = -(1 .. 16) a head:
    Mamba-2's ranges (published: `dt_bias` 1, A uniform in 0-16), for decays a token
    from near 1 down to exp(-4)."""
    E, F, V, X = cfg.d_model, cfg.d_mlp, cfg.vocab_size, cfg.held_experts
    H, Hkv, Dh, Hs, K = cfg.n_heads, cfg.kv_heads, cfg.d_head, cfg.ssm_heads, cfg.ssm_conv
    Dv, Dc, L = Hs * cfg.ssm_head_dim, _gdn_conv_width(cfg), cfg.n_layers
    (Ld, La), dt, g = _gdn_counts(cfg), cfg.param_dtype, dict(cfg.init_gains)
    keys = iter(jax.random.split(rng, 32))

    def n(shape, gain, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain / math.sqrt(fan_in))).astype(dt)

    step = jnp.exp(jax.random.uniform(next(keys), (Ld, Hs), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {
        "tok_embed": n((V, E), g["embed"], 1), "lm_head": n((E, V), g["head"], E),
        "ln_f_w": n((E,), g["norm"], 1),
        "ln1_w": n((L, E), g["norm"], 1), "ln2_w": n((L, E), g["norm"], 1),
        "gdn_w_qkvz": n((Ld, E, Dc + Dv), g["ssm_in"], E),
        "gdn_w_ba": n((Ld, E, 2 * Hs), g["ssm_ba"], E),
        "gdn_conv_w": n((Ld, K, Dc), g["ssm_conv"], K),
        "gdn_dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "gdn_A_log": jnp.log(jax.random.uniform(next(keys), (Ld, Hs), jnp.float32,
                                                1.0, 16.0)).astype(dt),
        "gdn_norm_w": jnp.ones((Ld, cfg.ssm_head_dim), dt),
        "gdn_w_out": n((Ld, Dv, E), g["ssm_out"], Dv),
        "ga_w_q": n((La, E, H, 2 * Dh), g["q"], E),
        "ga_w_kv": jnp.stack([n((La, E, Hkv, Dh), g["k"], E),
                              n((La, E, Hkv, Dh), g["v"], E)], axis=2),
        "ga_q_norm_w": n((La, Dh), g["norm"], 1), "ga_k_norm_w": n((La, Dh), g["norm"], 1),
        "ga_w_o": n((La, H, Dh, E), g["o"], H * Dh),
        "moe_router": n((L, E, cfg.moe_experts), g["router"], E),
        "moe_w_gate": n((L, X, E, F), g["mlp_in"], E),
        "moe_w_in": n((L, X, E, F), g["mlp_in"], E),
        "moe_w_out": n((L, X, F, E), g["expert_out"], F),
        "shared_w_gate": n((L, E, F), g["mlp_in"], E),
        "shared_w_in": n((L, E, F), g["mlp_in"], E),
        "shared_w_out": n((L, F, E), g["mlp_out"], F),
        "shared_gate": n((L, E), g["shared_gate"], E),
    }


def _gdn_layout(cfg: GPTConfig) -> KVLayout:
    """`kv_layout` of a `gdn_interval` model: ONE group as deep as the attention
    layers, a state a sequence for the delta layers (the convolution's last inputs
    as one row in the compute dtype; the float32 matrix [heads, key, value]),
    `slot_of` a layer's index among its kind."""
    from ..ops import delta

    n_delta, n_attn = _gdn_counts(cfg)
    slot_of = [l // cfg.gdn_interval if (l + 1) % cfg.gdn_interval == 0
               else l - l // cfg.gdn_interval for l in range(cfg.n_layers)]
    row = cfg.kv_heads * cfg.d_head
    return KVLayout(
        n_attn, (0,), (0,) * cfg.n_layers, tuple(slot_of), 1, row, row, n_delta,
        (("conv", ((cfg.ssm_conv - 1) * _gdn_conv_width(cfg),), jnp.dtype(cfg.dtype).name),
         ("gdn", delta.delta_state_shape(cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
          "float32")))


def _zero_centred_norm(x, w, eps):
    """x / rms(x) * (1 + w) over the last axis, float32 inside."""
    return rmsnorm(x, 1.0 + w.astype(jnp.float32), eps)


def _gdn_attention(cfg: GPTConfig, p, h, rope_tables, positions, attend):
    """The gated attention mixer over h [B, S, E]: `attend(q [B, H, S, Dh], k, v [B,
    Hkv, S, Dh]) -> (attention [B, H, S, Dh], store)`; `p` the layer's `ga_*`
    weights. Returns (what it adds to the stream, store)."""
    dt, Dh = cfg.dtype, cfg.d_head
    qg = jnp.einsum("bse,ehd->bhsd", h, p["w_q"].astype(dt))           # head n: [q_n | gate_n]
    kv = jnp.einsum("bse,etgd->tbgsd", h, p["w_kv"].astype(dt))
    q = _zero_centred_norm(qg[..., :Dh], p["q_norm_w"], cfg.norm_eps)
    k = _zero_centred_norm(kv[0], p["k_norm_w"], cfg.norm_eps)
    q, k = _rotary(cfg, rope_tables, positions, q, k)
    attn, store = attend(q, k, kv[1])
    gate = jax.nn.sigmoid(qg[..., Dh:].astype(jnp.float32))
    return _merge_heads((attn * gate).astype(dt), p["w_o"].astype(dt)), store


def _gdn_mlp(cfg: GPTConfig, params, l, h, valid):
    """Layer l's MLP over h [B, S, E] (normed): (held experts' part + the gated
    shared expert, the routing's load)."""
    dt = cfg.dtype
    y, load = _dropless_mlp(
        cfg, params["moe_router"][l],
        (params["moe_w_gate"], params["moe_w_in"], params["moe_w_out"]), h, h,
        layer=l, valid=valid)
    shared = _gated_mlp(cfg, h, *(params[k][l].astype(dt) for k in (
        "shared_w_gate", "shared_w_in", "shared_w_out")))
    gate = jax.nn.sigmoid(jnp.einsum(       # float32, as the router's logits
        "bse,e->bs", h.astype(jnp.float32), params["shared_gate"][l].astype(jnp.float32)))
    return y + (shared * gate[..., None]).astype(dt), load


def _gdn_layers(cfg: GPTConfig, params, x, store, valid, delta_net, attention):
    """The layer walk over x [B, S, E]. `store` is whatever the mixers keep between
    layers (the pool and the state arrays); it rides both scans' carry.
    `delta_net(store, i, p, h) -> (out, store)`: delta layer i (among its kind), `p`
    its weights under `ops/delta.py`'s names; `attention(store, i, p, h) -> (out,
    store)`: attention layer i. Returns (x under the FINAL norm, which `_logits` then
    leaves alone, store, the routing's load summed over the layers)."""
    dt, per = cfg.dtype, cfg.gdn_interval
    norm = lambda x, w: _zero_centred_norm(x, w, cfg.norm_eps)

    def layer(x, loads, l, mixer):
        out, kept = mixer(norm(x, params["ln1_w"][l]))
        x = x + out
        y, load = _gdn_mlp(cfg, params, l, norm(x, params["ln2_w"][l]), valid)
        return x + y, loads + load, kept

    def delta_layer(carry, idx):
        x, loads, store = carry
        l, i = idx
        p = {name: params["gdn_" + name][i].astype(dt) for name in _GDN_KEYS}
        x, loads, store = layer(x, loads, l, lambda h: delta_net(store, i, p, h))
        return (x, loads, store), None

    def period(carry, n):
        first = n * per
        carry, _ = jax.lax.scan(delta_layer, carry, (
            first + jnp.arange(per - 1), n * (per - 1) + jnp.arange(per - 1)))
        x, loads, store = carry
        p = {name[3:]: a[n] for name, a in params.items() if name.startswith("ga_")}
        x, loads, store = layer(x, loads, first + per - 1,
                                lambda h: attention(store, n, p, h))
        return (x, loads, store), None

    loads = jnp.zeros((5 if cfg.moe_held else 2,), jnp.float32)
    (x, loads, store), _ = jax.lax.scan(
        period, (x, loads, store), jnp.arange(cfg.n_layers // per))
    return norm(x, params["ln_f_w"]), store, loads


def _gdn_paged(cfg: GPTConfig, params, x, kv, attend, real, pos, state_slots, rope_tables):
    """`_paged_layers` for a `gdn_interval` model, from its embedding x [B, S, E] on:
    the attention layers through `_paged_layers`' own `attend` over a pool as deep as
    they are many, the delta layers from the state of lane b's slot `state_slots[b]`
    in kv["state"] (gathered, advanced over the lane's real tokens, written back in
    place), a lane whose first token sits at position 0 from a ZERO state, as a
    state-space layer of `ssm_layout` does. Returns what `_paged_layers` returns."""
    if state_slots is None:
        raise NotImplementedError(
            "a model with gated delta-net layers (gdn_interval) is served by "
            "prefill_paged and decode_step_paged, which name each lane's state slot; "
            "a verify step would have to roll the state back past the drafts it rejects")
    from ..ops import delta

    B = x.shape[0]
    fresh = (pos[:, 0] == 0)[:, None]
    tail_shape = (B, cfg.ssm_conv - 1, _gdn_conv_width(cfg))

    def delta_net(store, i, p, h):
        kk, vv, conv, state = store
        tail = jnp.where(fresh, 0, conv[i, state_slots])
        s0 = jnp.where(fresh[..., None, None], 0, state[i, state_slots])
        out, tail, s = delta.gated_delta_mixer(
            p, h, tail.reshape(tail_shape), s0, real, key_heads=cfg.ssm_groups,
            chunk=cfg.ssm_chunk, eps=cfg.norm_eps)
        return out, (kk, vv, conv.at[i, state_slots].set(tail.reshape(B, -1)),
                     state.at[i, state_slots].set(s))

    def attention(store, i, p, h):
        kk, vv, conv, state = store
        out, (kk, vv) = _gdn_attention(
            cfg, p, h, rope_tables, pos,
            lambda q, k, v: attend(kk, vv, i, None, q, k, v, None))
        return out, (kk, vv, conv, state)

    st = kv["state"]
    x, (kk, vv, conv, state), loads = _gdn_layers(
        cfg, params, x, (kv["k"], kv["v"], st["conv"], st["gdn"]), real, delta_net, attention)
    return (x, {"k": kk, "v": vv, "state": {"conv": conv, "gdn": state}},
            loads / cfg.n_layers, None)


def _gdn_forward(params, tokens, cfg: GPTConfig, return_aux: bool):
    """`forward` of a `gdn_interval` model: the whole sequence, every sequence's
    state from zero and dropped at the end, plain causal attention."""
    from ..ops import delta

    B, S = tokens.shape
    x = _embed(params, tokens, None, cfg)
    everyone, positions = jnp.ones((B, S), bool), jnp.arange(S)
    tail = jnp.zeros((B, cfg.ssm_conv - 1, _gdn_conv_width(cfg)), cfg.dtype)
    s0 = jnp.zeros((B, *delta.delta_state_shape(cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim)),
                   jnp.float32)
    rope_tables = _rope_tables(cfg)

    def delta_net(store, i, p, h):
        return delta.gated_delta_mixer(p, h, tail, s0, everyone, key_heads=cfg.ssm_groups,
                                       chunk=cfg.ssm_chunk, eps=cfg.norm_eps)[0], store

    def attention(store, i, p, h):
        return _gdn_attention(
            cfg, p, h, rope_tables, positions,
            lambda q, k, v: (_attention_plain(cfg, q, k, v, positions), store))

    x, _, _ = _gdn_layers(cfg, params, x, (), everyone, delta_net, attention)
    logits = _logits(params, x, cfg)
    return (logits, jnp.zeros((), jnp.float32)) if return_aux else logits


def qwen3_next_80b_a3b(**kw):
    """Qwen3-Next-80B-A3B-Instruct (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct,
    `model_type: "qwen3_next"`): 48 layers of 2048 under zero-centred RMSNorms (eps
    1e-6), layer l gated attention where (l + 1) % 4 == 0 (16 query heads over 2 K/V
    heads of 256, a norm a query and a key head, rotary over the first 64 features,
    theta 1e7, an elementwise sigmoid gate on the output) and else a gated delta net
    (32 value heads of 128 served by 16 key heads of 128, 4 taps, chunks of 64); in
    every layer 512 softmax-routed SiLU-gated experts of 512 (top-10, normalised)
    beside one shared expert under a sigmoid gate a token; vocabulary 151,936, untied
    head; no bias. Serving only: `forward` and the paged programs. The benchmark runs
    stage 0 of six (8 layers), 128 of the 512 experts held, a quarter of the
    vocabulary (benchmarks/configs)."""
    return GPTConfig(
        **{
            **dict(
                n_layers=48,
                gdn_interval=4,
                d_model=2048,
                n_heads=16,
                n_kv_heads=2,
                d_head=256,
                d_mlp=512,
                vocab_size=151936,
                max_seq=262144,
                norm="rmsnorm",
                norm_eps=1e-6,
                activation="swiglu",
                pos="rotary",
                rotary_dim=64,
                rope_theta=1e7,
                tie_embeddings=False,
                ssm_heads=32,
                ssm_head_dim=128,
                ssm_groups=16,
                ssm_state=128,
                ssm_conv=4,
                ssm_chunk=64,
                mlp_type="moe",
                moe_routing="dropless",
                moe_experts=512,
                moe_top_k=10,
                moe_scoring="softmax",
                moe_router_in="mlp",
                moe_shared=1,
                param_dtype=jnp.bfloat16,
                # `laguna_xs2`'s gains for what the two share (a stream of 1.5, the
                # router, the experts' and the shared expert's outputs); the delta
                # net's by `scripts/qwen3next_tolerance.py` on the chip (PERF.md §6,
                # PR 58). No program's shape or time depends on the numbers.
                init="unit_stream",
                init_gains=(("embed", 1.5), ("head", 1.0), ("norm", 0.1), ("q", 1.5),
                            ("k", 1.5), ("v", 1.0), ("o", 0.9), ("router", 2.0),
                            ("mlp_in", 1.0), ("mlp_out", 0.5), ("expert_out", 0.1),
                            ("shared_gate", 1.0), ("ssm_in", 1.0), ("ssm_ba", 1.0),
                            ("ssm_conv", 1.0), ("ssm_out", 1.0)),
                attn_impl="ref",
            ),
            **kw,
        }
    )


CONFIGS["qwen3-next-80b-a3b"] = qwen3_next_80b_a3b
