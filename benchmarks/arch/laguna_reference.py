"""Plain reference of the Laguna layer stack (poolside, `model_type:
"laguna"`), written from the published `config.json` alone: float32
`jax.numpy`, matmuls at `highest` precision, no cache, no paging, no kernel, no
batching, one sequence and ONE LAYER at a time. Independent of
`ray_tpu/models/gpt.py`, `ray_tpu/ops/moe.py` and `ray_tpu/ops/rope.py` (its
own rotary tables, YaRN included, its own window mask, the sum over ALL the
experts): it shares only the layout of the parameter tree, which is the
interface under test (`lead_*` [dense layers, ...]; the full layers'
attention `w_q`, `w_kv`, `w_o`, `w_head_gate` [full layers behind them, ...];
the window layers' `win_*` [window layers, ...]; norms, router, shared expert
and experts [layers behind the dense ones, ...]).

The layer, as this repo reads the config (x the residual stream [T, E], N =
RMSNorm with eps `rms_norm_eps`, layer l):

1. h = N1(x); q = h Wq with H_l query heads of `head_dim`, H_l =
   `num_attention_heads_per_layer[l]`; k = h Wk, v = h Wv with
   `num_key_value_heads` heads; no bias (`attention_bias` false).
2. Rotary by `layer_types[l]` from `rope_parameters`: the first
   `partial_rotary_factor` x head_dim features of every q and k head, as two
   halves (x[i], x[i + n/2]), by angle pos x inv_freq_i, inv_freq_i =
   theta^(-2i/n); the rest of the head untouched. `rope_type` "yarn": with
   low = floor(d(beta_fast)), high = ceil(d(beta_slow)), d(r) = n ln(P / (2 pi
   r)) / (2 ln theta), P the original positions, a ramp r_i = clip((i - low) /
   (high - low), 0, 1) over the n/2 frequencies, inv_freq_i = inv_freq_i (1 -
   r_i) + inv_freq_i / factor x r_i, and cos and sin times `attention_factor`.
3. Causal attention, scale 1 / sqrt(head_dim), query head i reads K/V head
   i // (H_l / Hkv); on a `sliding_attention` layer query i sees keys j with
   i - `sliding_window` < j <= i.
4. `gating`: g = sigmoid(h Wg), one number a head a token; head n's output
   times g_n; then x = x + concat(heads) Wo.
5. m = N2(x). A `dense` layer (`mlp_layer_types`): x = x + W_down(silu(W_gate
   m) * (W_up m)), `intermediate_size` wide. A `sparse` layer: r = m W_router,
   one logit an expert, float32; s = sigmoid(r); the `num_experts_per_tok`
   largest of s by a plain sort; their scores over their sum, times
   `moe_routed_scaling_factor`; x = x + sum_e w_e Expert_e(m) + Shared(m),
   each a SiLU-gated MLP (`moe_intermediate_size`,
   `shared_expert_intermediate_size`). No token is ever dropped.
6. Final N, untied head.

Departures and readings of the config, noted (the configuration file's
`assumed` says why for each): rotary as halves and the attention factor on
the tables; the gate one a head (the sibling config's "per-head"); sigmoid
scores normalised over the kept ones; no normalisation of q or k; no gate on
the shared expert. Fitting the chip beside the engine (the harness runs the
reference inside the replica, next to 7.7 GB of weights and the pool): the
tree stays bfloat16 and one layer's weights at a time are widened to float32,
the experts ONE at a time where they lie in their stacks, attention a block
of queries at a time, the head a block of rows and of vocabulary columns at a
time, and `make_logits` hands back a HOST array. None of these changes a
sum's terms.

Switches in `m` that make a WRONG reference, which a sound program must fail
(the benchmark's controls, `scripts/laguna_tolerance.py`): `window` (another
width), `rope_swapped` (each kind rotates by the other's table), `gate`
false, `shared_expert` false, `top_k` (another count)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # queries a block of attention
ROW_BLOCK = 512        # rows a block of the head
COL_BLOCK = 16384      # vocabulary columns a block of the head


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def rotary_table(rope: dict, head_dim: int, T: int):
    """(cos, sin) [T, n/2] float32 of one `rope_parameters` group, n the
    rotated features of a head: step 2 of the module docstring."""
    n = int(head_dim * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    amp = 1.0
    if rope["rope_type"] == "yarn":
        def d(turns):
            return n * math.log(rope["original_max_position_embeddings"]
                                / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(d(rope["beta_fast"])), 0)
        high = min(math.ceil(d(rope["beta_slow"])), n - 1)
        ramp = np.clip((np.arange(n // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
        inv = inv * (1.0 - ramp) + inv / rope["factor"] * ramp
        amp = float(rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default | yarn")
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * amp, jnp.float32),
            jnp.asarray(np.sin(ang) * amp, jnp.float32))


def _rotate(x, table):
    """x [T, heads, Dh] at positions 0..T-1: the first n features of every
    head as two halves, the rest as it is."""
    c, s = (a[:, None, :] for a in table)
    n = 2 * c.shape[-1]
    x1, x2 = x[..., : n // 2], x[..., n // 2: n]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., n:]], -1)


def _attention(q, k, v, window):
    """q [T, H, Dh], k and v [T, Hkv, Dh]; `window`: keys a query sees,
    itself included (None: every earlier one). A block of queries at a time."""
    T, H, Dh = q.shape
    rep = H // k.shape[1]
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, H, Dh)
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    j = jnp.arange(T)[None, :]

    def block(args):
        q_blk, i0 = args
        i = (i0 + jnp.arange(Q_BLOCK))[:, None]
        see = j <= i
        if window is not None:
            see = see & (j > i - window)
        att = jnp.einsum("shd,thd->hst", q_blk, kk) * Dh ** -0.5
        att = jax.nn.softmax(jnp.where(see[None], att, -jnp.inf), -1)
        return jnp.einsum("hst,thd->shd", att, vv)

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return out.reshape(-1, H, Dh)[:T]


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _experts(mlp_in, stacks, s, router, m):
    """Step 5's routed sum for all tokens: per-token top-k of the sigmoid
    scores by a plain sort, then one expert at a time over ALL tokens under
    its weight (0 for a token that did not choose it), read out of the
    stacks [layers, experts, ...] at layer `s` where it lies."""
    score = jax.nn.sigmoid(mlp_in @ _f32(router))               # [T, X]
    T, X = score.shape
    order = jnp.argsort(-score, axis=-1)[:, : m["top_k"]]
    kept = jnp.take_along_axis(score, order, axis=-1)
    w = kept / kept.sum(-1, keepdims=True) * m["route_scale"]
    weight = jnp.zeros((T, X), jnp.float32).at[jnp.arange(T)[:, None], order].set(w)

    def one(y, e):
        wg, wu, wd = (a[s, e] for a in stacks)
        return y + weight[:, e, None] * _gated(mlp_in, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(mlp_in), jnp.arange(X))
    return y


def layer_plan(m: dict):
    """[(lead index or None, index behind the dense layers or None, index
    among the layers of its kind in its stack, is a window layer)] a layer."""
    D, plan, seen = m["dense_layers"], [], [0, 0]
    for l, win in enumerate(m["window_layout"]):
        if l < D:
            plan.append((l, None, l, 0))
        else:
            plan.append((None, l - D, seen[win], win))
            seen[win] += 1
    return plan


@functools.partial(jax.jit, static_argnames=("where", "swapped", "spec"))
def _layer(x, params, tables, where, swapped, spec):
    """One layer over the whole sequence. `where` = its entry of `layer_plan`;
    `tables` = (the full layers' rotary table, the window layers'), each kind
    taking the other's under `swapped`; `spec` = (window, gate, shared expert,
    top_k, route scale, eps)."""
    lead, s, i, win = where
    window, gate, shared, top_k, route_scale, eps = spec
    if lead is not None:
        attn = {k: params["lead_" + k][lead] for k in ("w_q", "w_kv", "w_o", "w_head_gate")}
        norms = (params["lead_ln1_w"][lead], params["lead_ln2_w"][lead])
    else:
        pre = "win_" if win else ""
        attn = {k: params[pre + k][i] for k in ("w_q", "w_kv", "w_o", "w_head_gate")}
        norms = (params["ln1_w"][s], params["ln2_w"][s])
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norms[0], eps)                                   # step 1
        q = jnp.einsum("te,ehd->thd", h, _f32(attn["w_q"]))
        kv = jnp.einsum("te,eghd->gthd", h, _f32(attn["w_kv"]))
        table = tables[win ^ swapped]                                # step 2
        a = _attention(_rotate(q, table), _rotate(kv[0], table), kv[1],
                       window if win else None)                      # step 3
        if gate:                                                     # step 4
            a = a * jax.nn.sigmoid(h @ _f32(attn["w_head_gate"]))[..., None]
        x = x + jnp.einsum("thd,hde->te", a, _f32(attn["w_o"]))
        mlp_in = _rms(x, norms[1], eps)                              # step 5
        if lead is not None:
            return x + _gated(mlp_in, *(params["lead_" + k][lead]
                                        for k in ("w_gate", "w_in", "w_out")))
        y = _experts(mlp_in, tuple(params[k] for k in ("moe_w_gate", "moe_w_in", "moe_w_out")),
                     s, params["moe_router"][s], {"top_k": top_k, "route_scale": route_scale})
        if shared:
            y = y + _gated(mlp_in, *(params[k][s] for k in
                                     ("shared_w_gate", "shared_w_in", "shared_w_out")))
        return x + y


def hidden(params, tokens, m: dict):
    """tokens [T] int32 -> the residual stream after the final norm [T, E]."""
    T = tokens.shape[0]
    x = _f32(params["tok_embed"][tokens])
    tables = (rotary_table(m["rope"]["full_attention"], m["d_head"], T),
              rotary_table(m["rope"]["sliding_attention"], m["d_head"], T))
    spec = (m["window"], m.get("gate", True), m.get("shared_expert", True),
            m["top_k"], m["route_scale"], m["norm_eps"])
    layer_keys = [k for k in params if k not in ("tok_embed", "lm_head", "ln_f_w")]
    stack = {k: params[k] for k in layer_keys}
    for where in layer_plan(m):
        x = _layer(x, stack, tables, where, int(bool(m.get("rope_swapped"))), spec)
    return _rms(x, params["ln_f_w"], m["norm_eps"])                  # step 6


def _head_rows(x_rows, head):
    return jnp.concatenate(
        [x_rows @ _f32(head[:, c: c + COL_BLOCK])
         for c in range(0, head.shape[1], COL_BLOCK)], axis=-1)


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""

    @jax.jit
    def head_rows(x_rows, head):
        with jax.default_matmul_precision("highest"):
            return _head_rows(x_rows, head)

    def fn(params, tokens):
        x = hidden(params, jnp.asarray(tokens, jnp.int32), m)
        return np.concatenate(
            [np.asarray(head_rows(x[r: r + ROW_BLOCK], params["lm_head"]))
             for r in range(0, x.shape[0], ROW_BLOCK)], axis=0)

    return fn


def make_loss(m: dict):
    """(params, tokens [S+1]) -> summed next-token cross-entropy (float32)."""
    logits = make_logits(m)

    def loss(params, tokens):
        lg = logits(params, tokens[:-1])
        logp = lg - np.logaddexp.reduce(lg, axis=-1, keepdims=True)
        return float(-np.take_along_axis(logp, np.asarray(tokens[1:])[:, None], -1).sum())

    return loss
