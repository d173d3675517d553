"""Plain reference of the A.X-K1 layer stack (SK Telecom, `model_type: "axk1"`;
the equations are DeepSeek-V3's): float32 `jax.numpy`, matmuls at `highest`
precision, a full forward over one whole sequence, the EXPANDED form of the
latent attention, no cache, no paging, no batching, no kernels. Independent of
`ray_tpu/models/gpt.py` and `ray_tpu/ops/moe.py`: it shares only the layout of
the parameter tree, which is the interface under test.

One layer, x the residual stream [T, E], N = RMSNorm (eps from `rms_norm_eps`):

1. h = N(x); c_q = N(h W_DQ) [q_lora_rank]; q = c_q W_UQ -> H heads of
   [q_nope | q_rope]; [c_kv | k_r] = h W_DKV [kv_lora_rank + qk_rope_head_dim];
   c = N(c_kv); per head [k_nope | v] = c W_UKV^h (`kv_b` holds both).
2. k = [k_nope | RoPE(k_r)], k_r shared by all heads; q = [q_nope | RoPE(q_rope)].
   RoPE rotates the INTERLEAVED pairs (x[2i], x[2i+1]) (*assumed*, as the
   family's modelling code: it un-interleaves and then rotates halves, the
   same rotation under a permutation q and k share) by YaRN's frequencies:
   with d = qk_rope_head_dim and f_i = theta^(-2i/d), the dimension that
   turns r times over the original positions is n(r) = d ln(orig / (2 pi
   r)) / (2 ln theta); ramp_i = clip((i - floor n(beta_fast)) / (ceil
   n(beta_slow) - floor n(beta_fast)), 0, 1); the frequency is f_i (1 -
   ramp_i) + (f_i / factor) ramp_i; cos and sin are scaled by mscale(factor,
   `mscale`) / mscale(factor, `mscale_all_dim`), mscale(s, a) = 0.1 a ln s + 1.
3. Causal softmax(q k^T s) v, s = (qk_nope + qk_rope)^-0.5 x mscale(factor,
   `mscale_all_dim`)^2; heads concatenated through W_O. x = x + attention.
4. g = N(x). In the `first_k_dense_replace` leading layers y = W_down(silu(
   W_gate g) * (W_up g)) at `intermediate_size`. In the others: scores =
   sigmoid(g W_r) [n_routed_experts, the PUBLISHED width] in float32; the
   `num_experts_per_tok` largest by a plain sort (`topk_method: "none"` read
   literally, *assumed*: no group limit, no correction bias); weights = those
   scores over their sum, x `routed_scaling_factor`; y = shared(g) + the sum
   over the chosen experts HELD HERE (`held_start` .. + `held_count`) of
   weight x expert(g), each silu(W_gate g) * (W_up g) then W_down. What an
   absent expert would add is left out, as the program leaves it out.
   x = x + y.
5. Final N, head over the vocabulary rows held here.

Departures, noted. (a) The program's tree carries an output bias `b_o`, MLP
biases and norm biases the published model does not have; zero at
initialisation, and the reference adds `b_o` and `b_out` as the tree gives
them. (b) Fitting the chip beside 9 GiB of weights and the pool (the harness
runs the reference inside the replica): attention is computed a block of
`Q_BLOCK` query rows at a time (64 heads x 6,064^2 float32 scores at once would
be 9.4 GB), one expert and one `F_BLOCK` columns of the dense MLP at a time is
widened to float32, the head a block of vocabulary columns at a time, and
`make_logits` hands back a HOST array. None of these changes a sum's terms.

Switches in `m` that make a WRONG reference, which a sound program must fail
(the benchmark's controls, `scripts/axk1_tolerance.py`): `scoring` "softmax"
(a softmax over the 192 logits in sigmoid's place), `attn_mscale` false (s
without mscale^2), `shared_expert` false, `held_start` shifted by one,
`rope_cols` "nope" (the rotation on the last qk_rope_head_dim columns of the
NOPE part instead of the rope part)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128          # query rows a block of attention
F_BLOCK = 2048         # columns of the dense MLP widened at a time
COL_BLOCK = 8192       # vocabulary columns a block of the head

_ATTN = ("ln1_w", "ln2_w", "w_dq", "q_norm_w", "w_uq", "w_dkv", "kv_norm_w",
         "w_ukv", "w_o", "b_o")
_DENSE = ("w_gate", "w_in", "w_out", "b_out")
_MOE = ("moe_router", "moe_w_gate", "moe_w_in", "moe_w_out",
        "shared_w_gate", "shared_w_in", "shared_w_out")


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(m: dict) -> np.ndarray:
    """Step 2's frequencies [qk_rope_head_dim / 2], float64, a loop as written."""
    d, theta, y = m["d_rope"], m["rope_theta"], m["yarn"]
    orig, factor = y["original_max_position_embeddings"], y["factor"]
    turns = lambda r: d * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(turns(y["beta_fast"])), 0)
    high = min(math.ceil(turns(y["beta_slow"])), d - 1)
    out = []
    for i in range(d // 2):
        f = theta ** (-2.0 * i / d)
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return np.asarray(out)


def _rotate(x, m: dict):
    """x [T, ..., d_rope] at positions 0..T-1: interleaved pairs, YaRN."""
    T, d = x.shape[0], x.shape[-1]
    y = m["yarn"]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_frequencies(m), jnp.float32)[None, :]
    amp = mscale(y["factor"], y["mscale"]) / mscale(y["factor"], y["mscale_all_dim"])
    c, s = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    while c.ndim < x.ndim:
        c, s = c[:, None], s[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _attention(q, k, v, scale):
    """Causal attention of q, k [T, H, Dqk], v [T, H, Dv], a block of queries
    at a time."""
    T, H, _ = q.shape
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, H, q.shape[-1])
    j = jnp.arange(T)[None, :]

    def block(args):
        q_blk, i0 = args
        i = (i0 + jnp.arange(Q_BLOCK))[:, None]
        att = jnp.einsum("shd,thd->hst", q_blk, k) * scale
        att = jax.nn.softmax(jnp.where((j <= i)[None], att, -jnp.inf), -1)
        return jnp.einsum("hst,thd->shd", att, v)

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return out.reshape(-1, H, v.shape[-1])[:T]


def _latent_attention(x, p, m: dict):
    """Steps 1-3: x [T, E] -> what attention adds to the stream [T, E]."""
    eps, Dn, Dr, R = m["norm_eps"], m["d_nope"], m["d_rope"], m["kv_lora"]
    h = _rms(x, _f32(p["ln1_w"]), eps)
    cq = _rms(h @ _f32(p["w_dq"]), _f32(p["q_norm_w"]), eps)
    q = jnp.einsum("tr,rhd->thd", cq, _f32(p["w_uq"]))            # [T, H, Dn + Dr]
    ckv = h @ _f32(p["w_dkv"])                                      # [T, R + Dr]
    c = _rms(ckv[:, :R], _f32(p["kv_norm_w"]), eps)
    kv = jnp.einsum("tr,rhd->thd", c, _f32(p["w_ukv"]))             # [T, H, Dn + Dv]
    k_nope, v = kv[..., :Dn], kv[..., Dn:]
    q_nope, q_rope, k_r = q[..., :Dn], q[..., Dn:], ckv[:, R:]
    if m.get("rope_cols", "rope") == "rope":
        q_rope, k_r = _rotate(q_rope, m), _rotate(k_r, m)
    else:       # WRONG: the rotation on the nope part's last Dr columns
        q_nope = jnp.concatenate(
            [q_nope[..., :Dn - Dr], _rotate(q_nope[..., Dn - Dr:], m)], -1)
        k_nope = jnp.concatenate(
            [k_nope[..., :Dn - Dr], _rotate(k_nope[..., Dn - Dr:], m)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None], k_nope.shape[:2] + (Dr,))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    scale = (Dn + Dr) ** -0.5
    if m.get("attn_mscale", True):
        scale *= mscale(m["yarn"]["factor"], m["yarn"]["mscale_all_dim"]) ** 2
    a = _attention(q, k, v, scale)
    return jnp.einsum("thd,hde->te", a, _f32(p["w_o"])) + _f32(p["b_o"])


def _gated(g, wg, wu, wd):
    return (jax.nn.silu(g @ _f32(wg)) * (g @ _f32(wu))) @ _f32(wd)


def _dense_mlp(g, p):
    """Step 4, a leading layer: `F_BLOCK` columns of the hidden width at a time."""
    F = p["w_in"].shape[-1]
    y = jnp.zeros_like(g)
    for f in range(0, F, F_BLOCK):
        y = y + _gated(g, p["w_gate"][:, f:f + F_BLOCK], p["w_in"][:, f:f + F_BLOCK],
                       p["w_out"][f:f + F_BLOCK])
    return y + _f32(p["b_out"])


def route(g, router, m: dict):
    """Step 4's gate [T, n_routed_experts]: a token's weight at each expert it
    chose, 0 elsewhere, over ALL the experts the router knows."""
    T = g.shape[0]
    logits = g @ _f32(router)
    if m.get("scoring", "sigmoid") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:       # WRONG for this model
        scores = jax.nn.softmax(logits, -1)
    order = jnp.argsort(-scores, axis=-1)[:, : m["top_k"]]          # [T, k]
    kept = jnp.take_along_axis(scores, order, axis=-1)
    w = kept / kept.sum(-1, keepdims=True) * m["route_scale"]
    return jnp.zeros_like(scores).at[jnp.arange(T)[:, None], order].set(w)


def _expert_mlp(g, p, m: dict):
    """Step 4, an expert layer: the shared expert, then one held expert at a
    time over all tokens under its gate weight."""
    gate = route(g, p["moe_router"], m)
    held = gate[:, m["held_start"]: m["held_start"] + m["held_count"]]

    def one(y, inp):
        wg, wu, wd, col = inp
        return y + col[:, None] * _gated(g, wg, wu, wd), None

    y = jnp.zeros_like(g)
    if m.get("shared_expert", True):
        y = _gated(g, p["shared_w_gate"], p["shared_w_in"], p["shared_w_out"])
    y, _ = jax.lax.scan(
        one, y, (p["moe_w_gate"], p["moe_w_in"], p["moe_w_out"], held.T))
    return y


def hidden(params, tokens, m: dict):
    """tokens [T] int32 -> the residual stream after the final norm [T, E]."""
    x = _f32(params["tok_embed"][tokens])
    eps = m["norm_eps"]
    for l in range(m["dense_layers"]):          # the leading dense layers
        p = {k: params["lead_" + k][l] for k in _ATTN + _DENSE}
        x = x + _latent_attention(x, p, m)
        x = x + _dense_mlp(_rms(x, _f32(p["ln2_w"]), eps), p)

    def layer(x, p):
        x = x + _latent_attention(x, p, m)
        return x + _expert_mlp(_rms(x, _f32(p["ln2_w"]), eps), p, m), None

    x, _ = jax.lax.scan(layer, x, {k: params[k] for k in _ATTN + _MOE})
    return _rms(x, _f32(params["ln_f_w"]), eps)


def make_hidden(m: dict):
    @jax.jit
    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return hidden(params, tokens, m)

    return fn


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V held] float32 as a HOST array."""
    hid = make_hidden(m)

    @jax.jit
    def head(x, w):
        with jax.default_matmul_precision("highest"):
            return x @ _f32(w)

    def fn(params, tokens):
        x = hid(params, jnp.asarray(tokens, jnp.int32))
        w = params["lm_head"]
        return np.concatenate(
            [np.asarray(head(x, w[:, c: c + COL_BLOCK]))
             for c in range(0, w.shape[1], COL_BLOCK)], axis=-1)

    return fn


def make_loss(m: dict):
    """(params, tokens [S+1]) -> summed next-token cross-entropy (float32)
    over the held vocabulary."""
    logits = make_logits(m)

    def loss(params, tokens):
        lg = logits(params, tokens[:-1])
        logp = lg - np.logaddexp.reduce(lg, axis=-1, keepdims=True)
        return float(-np.take_along_axis(logp, np.asarray(tokens[1:])[:, None], -1).sum())

    return loss
