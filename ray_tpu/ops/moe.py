"""Mixture-of-Experts layer with expert parallelism (absent from the
reference — SURVEY.md §2.6 "Expert parallel (EP/MoE): Absent"; first-class
here per the build plan).

TPU-idiomatic GShard/Switch design: token→expert routing is expressed as
dense one-hot dispatch/combine tensors and einsums — static shapes, no
sorts/gathers, everything lands on the MXU, and under pjit the expert axis
of the weights shards over the `ep` mesh axis (XLA inserts the all-to-alls).

Top-1 (Switch) and top-2 (GShard) gating with capacity dropping and the
standard load-balancing auxiliary loss.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2                  # 1 = Switch, 2 = GShard
    capacity_factor: float = 1.25
    d_model: int = 768
    d_ff: int = 3072
    aux_loss_weight: float = 0.01
    activation: str = "gelu"        # gelu | swiglu (adds w_gate per expert)
    dtype: object = jnp.bfloat16

    def capacity(self, num_tokens: int) -> int:
        c = int(self.capacity_factor * num_tokens * self.top_k / self.num_experts)
        return max(c, 4)


def moe_init(rng, cfg: MoEConfig) -> Dict[str, jnp.ndarray]:
    """Params with logical dims:
    w_router (embed, experts); w_in (experts, embed, mlp); w_out (experts, mlp, embed).
    """
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    s = 0.02
    params = {
        "w_router": jax.random.normal(k1, (D, E), jnp.float32) * s,
        "w_in": jax.random.normal(k2, (E, D, F), jnp.float32) * s,
        "w_out": jax.random.normal(k3, (E, F, D), jnp.float32) * s,
    }
    if cfg.activation == "swiglu":
        params["w_gate"] = jax.random.normal(k4, (E, D, F), jnp.float32) * s
    return params


def _one_hot_dispatch(gate_idx, probs, mask, capacity, num_experts):
    """Build dispatch/combine slices for one routing choice.

    gate_idx [N] expert per token; mask [N] tokens still in play;
    returns (dispatch [N, E, C] one-hot, gate_probs [N] prob of this choice,
    kept [N] capacity mask).
    """
    expert_mask = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32) * mask[:, None]
    # Position of each token within its expert's buffer (cumulative count).
    position = jnp.cumsum(expert_mask, axis=0) * expert_mask  # [N, E]
    position = position.sum(axis=-1) - 1.0                    # [N], -1 if masked
    kept = (position >= 0) & (position < capacity)
    pos_oh = jax.nn.one_hot(position.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = expert_mask[:, :, None] * pos_oh[:, None, :] * kept[:, None, None]
    gate_probs = (probs * expert_mask).sum(axis=-1)
    return dispatch, gate_probs, kept


def moe_router(x_flat, w_router, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x_flat [N, D] → (combine [N, E, C], aux_loss scalar).

    combine holds the gating weight of each (token, expert, slot); dispatch
    is its boolean support.
    """
    N = x_flat.shape[0]
    E = cfg.num_experts
    C = cfg.capacity(N)
    logits = x_flat.astype(jnp.float32) @ w_router  # router math in f32
    probs = jax.nn.softmax(logits, axis=-1)        # [N, E]

    gate1 = jnp.argmax(probs, axis=-1)
    disp1, p1, kept1 = _one_hot_dispatch(
        gate1, probs, jnp.ones(N, jnp.float32), C, E
    )

    # Load-balancing aux loss (Switch eq. 4): E * Σ_e f_e · P_e
    me = jax.nn.one_hot(gate1, E, dtype=jnp.float32).mean(axis=0)  # token fraction
    pe = probs.mean(axis=0)                                        # mean router prob
    aux = E * jnp.sum(me * pe)

    if cfg.top_k == 1:
        combine = disp1 * p1[:, None, None]
        return combine, aux

    # Top-2: mask out the first choice, route the remainder.
    probs2 = probs * (1.0 - jax.nn.one_hot(gate1, E, dtype=jnp.float32))
    gate2 = jnp.argmax(probs2, axis=-1)
    # Second-choice buffer positions start after all first-choice tokens.
    first_counts = jax.nn.one_hot(gate1, E, dtype=jnp.float32).sum(axis=0)  # [E]
    expert_mask2 = jax.nn.one_hot(gate2, E, dtype=jnp.float32)
    position2 = jnp.cumsum(expert_mask2, axis=0) * expert_mask2
    position2 = (position2 + first_counts[None, :] * expert_mask2).sum(axis=-1) - 1.0
    kept2 = (position2 >= 0) & (position2 < C)
    pos2_oh = jax.nn.one_hot(position2.astype(jnp.int32), C, dtype=jnp.float32)
    disp2 = expert_mask2[:, :, None] * pos2_oh[:, None, :] * kept2[:, None, None]
    p2 = (probs * expert_mask2).sum(axis=-1)

    # Renormalize the two gate probs over the kept choices.
    denom = p1 * kept1 + p2 * kept2
    denom = jnp.maximum(denom, 1e-9)
    combine = disp1 * (p1 * kept1 / denom)[:, None, None] + disp2 * (
        p2 * kept2 / denom
    )[:, None, None]
    return combine, aux


def moe_forward(params, x, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [..., D] → (y [..., D], aux_loss). Shard w_in/w_out on `ep` via
    logical dim "experts"; the dispatch einsum's [E, C, D] intermediate then
    shards on ep and XLA places the token all-to-alls on ICI."""
    orig_shape = x.shape
    D = orig_shape[-1]
    x_flat = x.reshape(-1, D)

    combine, aux = moe_router(x_flat, params["w_router"], cfg)
    combine = combine.astype(cfg.dtype)
    dispatch = (combine > 0).astype(cfg.dtype)

    xc = x_flat.astype(cfg.dtype)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xc)         # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"].astype(cfg.dtype))
    if cfg.activation == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"].astype(cfg.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(cfg.dtype))
    y = jnp.einsum("nec,ecd->nd", combine, expert_out)          # [N, D]
    return y.reshape(orig_shape), cfg.aux_loss_weight * aux


MOE_LOGICAL_DIMS = {
    "w_router": ("embed", "experts"),
    "w_in": ("experts", "embed", "mlp"),
    "w_out": ("experts", "mlp", "embed"),
    "w_gate": ("experts", "embed", "mlp"),
}


# ------------------------------------------------------- dropless top-k
# Beside the capacity-dropping GShard path above (training configurations):
# routing for any k with NO capacity, so no token is ever dropped, and gate
# weights that are a softmax over the k kept logits (the same as a softmax
# over all experts renormalised over the kept ones). Static shapes without a
# capacity mean the experts are applied densely and masked: every expert
# sees every token, and the [N, X] combine matrix is zero where a token did
# not choose the expert. `touched_k` adds the small-batch form a decode
# step wants: when the step's N*k assignments cannot reach every expert, a
# loop over the experts that were chosen reads only those experts' weights.


def dropless_route(logits, top_k: int, scoring: str = "softmax", scale: float = 1.0):
    """logits [N, X] (any float) -> (idx [N, k] int32, weights [N, k] f32):
    the k largest logits of each token and, as `scoring` says, the softmax
    over those k ("softmax") or their sigmoids over the sum of those k, times
    `scale` ("sigmoid": the sigmoid keeps the logits' order, so the k largest
    scores are the k largest logits)."""
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    if scoring == "softmax":
        return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)
    if scoring != "sigmoid":
        raise ValueError(f"dropless scoring {scoring!r}: softmax | sigmoid")
    kept = jax.nn.sigmoid(vals)
    return idx.astype(jnp.int32), kept / kept.sum(axis=-1, keepdims=True) * scale


def dropless_combine(idx, weights, num_experts: int):
    """[N, k] choices -> combine [N, X] f32 (a token's gate weight at each
    expert it chose, 0 elsewhere)."""
    return (jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)
            * weights[..., None]).sum(axis=-2)


def _gated(act: str, g, u):
    if act == "reglu":
        return jax.nn.relu(g) * u
    if act == "swiglu":
        return jax.nn.silu(g) * u
    raise ValueError(f"dropless experts are gated (reglu | swiglu), got {act!r}")


def dropless_experts(x, combine, w_gate, w_in, w_out, activation: str,
                     layer=None, touched_k: int = 0):
    """y [N, D] = sum_e combine[n, e] * W_out,e( act(W_gate,e x) * (W_in,e x) ).

    x [N, D]; combine [N, X] f32; weights [X, D, F] / [X, F, D], or with
    `layer` (a traced index) the whole stacks [L, X, ...] of which layer
    `layer` is read in place. Two forms, the same mathematics:

    * dense (default): one [N, D] x [D, X*F] product for gate and up, the
      combine weights folded into the hidden activations, one [N, X*F] x
      [X*F, D] product down. Reads every expert once: right from some ten
      tokens up, where top-k of N tokens reaches most experts anyway.
    * `touched_k` = k > 0: a loop over at most min(N*k, X) experts, the chosen
      ones first, each applied to all N tokens under its combine column;
      an expert nobody chose is never read. Right for a decode step of a
      few lanes, whose time is the bytes of expert weights it streams.
    """
    N, D = x.shape
    X = combine.shape[-1]
    dt = x.dtype

    def one(a, e):
        """Expert e's slice of a stacked weight, read where it lies."""
        if layer is None:
            return jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False)
        return jax.lax.dynamic_slice(
            a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0]

    if not touched_k:
        if layer is not None:
            w_gate, w_in, w_out = (
                jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                for a in (w_gate, w_in, w_out))
        g = jnp.einsum("nd,xdf->nxf", x, w_gate.astype(dt))
        u = jnp.einsum("nd,xdf->nxf", x, w_in.astype(dt))
        h = _gated(activation, g, u) * combine[..., None].astype(dt)
        return jnp.einsum("nxf,xfd->nd", h, w_out.astype(dt))

    hit = (combine > 0).any(axis=0)                      # [X] chosen by someone
    order = jnp.argsort(~hit, stable=True)               # chosen experts first
    n_hit = hit.sum()

    def body(i, y):
        def run(y):
            e = order[i]
            g = x @ one(w_gate, e).astype(dt)
            u = x @ one(w_in, e).astype(dt)
            c = jax.lax.dynamic_index_in_dim(combine, e, 1, keepdims=True)
            h = _gated(activation, g, u) * c.astype(dt)
            return y + (h @ one(w_out, e).astype(dt)).astype(jnp.float32)

        return jax.lax.cond(i < n_hit, run, lambda y: y, y)

    trips = min(N * touched_k, X)
    y = jax.lax.fori_loop(0, trips, body, jnp.zeros((N, D), jnp.float32))
    return y.astype(dt)


def dropless_load(combine, valid=None, top_k: int = 0):
    """(experts with at least one token, the busiest expert's share of the
    assignments) of one layer's routing, f32 scalars; `valid` [N] leaves
    padding tokens out. `combine` cut to the columns of the experts held
    here, with `top_k` given: also (the assignments that fell on those
    columns, all the tokens' assignments = tokens x top_k)."""
    chosen = combine > 0
    if valid is not None:
        chosen = chosen & valid[:, None]
    per = chosen.sum(axis=0).astype(jnp.float32)         # [X] assignments
    load = ((per > 0).sum().astype(jnp.float32), per.max() / jnp.maximum(per.sum(), 1.0))
    if not top_k:
        return load
    tokens = combine.shape[0] if valid is None else valid.sum()
    return (*load, per.sum(), jnp.asarray(tokens * top_k, jnp.float32))
