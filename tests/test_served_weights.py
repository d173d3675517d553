"""The form an engine holds the fused q/k/v stack in (`models/gpt.py`
`hold_served`, `_project_served`; PERF.md §6, PR 50) against the public one:
the product from the re-formed stack is the named product, an engine given a
public tree generates what the public tree gives through the same paged entry
points, a tree without the fused stack is held as it came, and the public
form itself is where it was."""

import numpy as np
import pytest

OURO = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "max_position_embeddings": 256, "vocab_size": 300, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "total_ut_steps": 4, "early_exit_threshold": 1,
    "program_model": "ouro-2.6b",
}


def _cfg(model):
    import jax.numpy as jnp

    from benchmarks.arch import ouro as arch
    from ray_tpu.models import gpt

    f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    if model == "ouro":
        name, overrides = arch.program(OURO, arch.dims(OURO, False))
        return gpt.CONFIGS[name](**overrides, **f32)
    return gpt.CONFIGS[model](n_layers=2, vocab_size=512, max_seq=256, **f32)


@pytest.mark.parametrize("tokens", [(4, 1), (1, 32), (2, 5)],
                         ids=["decode", "chunk", "verify"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_product_from_the_held_stack_is_the_named_product(dtype, tokens):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    dt = jnp.dtype(dtype)
    cfg = gpt.CONFIGS["gpt2-small"](dtype=dt, param_dtype=dt, remat=False)
    L, E, H, D = 2, cfg.d_model, cfg.n_heads, cfg.d_head
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    public = {
        "w_qkv": (jax.random.normal(k[0], (L, E, 3, H, D)) * 0.02).astype(dt),
        "b_qkv": (jax.random.normal(k[1], (L, 3, H, D)) * 0.5).astype(dt),   # not zero
    }
    held, moved = gpt.hold_served(public)
    assert set(held) == {"w_qkv_served", "b_qkv"} and held["b_qkv"] is public["b_qkv"]
    assert held["w_qkv_served"].shape == (L, 3, H, D, E)
    assert moved == public["w_qkv"].nbytes and set(public) == {"w_qkv", "b_qkv"}
    h = jax.random.normal(k[2], (*tokens, E)).astype(dt)
    project = jax.jit(lambda p, h: gpt._project_qkv(cfg, p, h))
    for layer in range(L):
        want = project({n: a[layer] for n, a in public.items()}, h)
        named = jnp.einsum("bse,ethd->btshd", h, public["w_qkv"][layer]) \
            + public["b_qkv"][layer][:, None]
        got = project({n: a[layer] for n, a in held.items()}, h)
        for t, (g, w) in enumerate(zip(got, want)):
            assert g.shape == w.shape == (*tokens, H, D) and g.dtype == w.dtype == dt
            g, w, n = (np.asarray(a, np.float32) for a in (g, w, named[:, t]))
            assert np.abs(w).max() > 0.3
            if dtype == "float32":
                np.testing.assert_array_equal(g, w)
                np.testing.assert_array_equal(g, n)
            else:       # one bfloat16 rounding of numbers near 1
                np.testing.assert_allclose(g, n, atol=2.0 ** -7, rtol=2.0 ** -7)


@pytest.mark.parametrize("model", ["gpt2-small", "ouro"])
def test_an_engine_given_a_public_tree_generates_the_public_trees_tokens(model, monkeypatch):
    """The engine's own tree (held form) against an engine that holds the
    caller's tree as it came, which is the parent commit's engine: the same
    prompts through chunked prefill and decode, greedy, float32."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    cfg = _cfg(model)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    params["b_qkv"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), params["b_qkv"].shape)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (20, 37)]

    def generate():
        eng = InferenceEngine(cfg, params=params, options=EngineOptions(
            num_blocks=40, block_size=8, max_num_seqs=4, max_step_tokens=64,
            prefill_chunk_tokens=24, host_kv_bytes=0))
        rids = [eng.submit(p, 24) for p in prompts]
        while eng.scheduler.has_work():
            eng.step()
        return eng, [list(eng.stream(r)) for r in rids]

    eng, got = generate()
    assert "w_qkv" not in eng.params and "w_qkv" in params     # the caller's: untouched
    assert eng.params["w_qkv_served"].shape == (
        cfg.n_layers, 3, cfg.n_heads, cfg.d_head, cfg.d_model)
    assert eng.stats()["weights_reformed_bytes"] == params["w_qkv"].nbytes
    monkeypatch.setattr(gpt, "hold_served", lambda tree: (tree, 0))
    parent, want = generate()
    assert parent.params is params and parent.stats()["weights_reformed_bytes"] == 0
    assert got == want and all(len(t) == 24 for t in got)


@pytest.mark.parametrize("model", ["smallthinker-21b-a3b", "ax-k1", "jamba2-3b", "laguna-xs2"])
def test_a_tree_without_the_fused_stack_is_held_as_it_came(model):
    import jax

    from ray_tpu.models import gpt

    cfg = gpt.CONFIGS[model](remat=False)
    tree = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
    assert "w_qkv" not in tree
    held, moved = gpt.hold_served(tree)
    assert held is tree and moved == 0


def test_an_engine_holds_a_grouped_query_tree_by_identity():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    cfg = gpt.GPTConfig(
        vocab_size=128, n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_mlp=64, max_seq=64, attn_impl="ref", remat=False, dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params=params, options=EngineOptions(
        num_blocks=8, block_size=8, max_num_seqs=2, host_kv_bytes=0))
    assert eng.params is params and eng.stats()["weights_reformed_bytes"] == 0


@pytest.mark.parametrize("model", ["gpt2-small", "gpt2-large", "ouro-2.6b"])
def test_the_public_tree_keeps_its_form(model):
    """`init_params`, `param_logical_dims` and the Hugging Face bridge read
    w_qkv as [L, E, 3, H, Dh]; the held form has no entry of its own there."""
    import jax

    from ray_tpu.models import gpt

    cfg = gpt.CONFIGS[model]()
    tree = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
    shape = (cfg.n_layers, cfg.d_model, 3, cfg.n_heads, cfg.d_head)
    assert tree["w_qkv"].shape == shape and "w_qkv_served" not in tree
    dims = gpt.param_logical_dims(cfg)
    assert dims["w_qkv"] == ("layers", "embed", None, "heads", "head_dim")
    assert "w_qkv_served" not in dims
    held = jax.eval_shape(lambda t: gpt.hold_served(t)[0], tree)
    assert held["w_qkv_served"].shape == (shape[0], 3, *shape[3:], shape[1])
    assert {k: v for k, v in held.items() if k != "w_qkv_served"} == {
        k: v for k, v in tree.items() if k != "w_qkv"}


def test_the_rehearsal_counts_the_bytes_a_program_moves_of_its_parameters():
    """`scripts.paged_rehearse.param_relayout_bytes` on a program's text: the
    `copy` / `transpose` / `copy-start` results of the entry computation whose
    operand is a parameter (through bitcasts too) other than the pool; what a
    fusion holds, what a loop's body moves, a computed array and a vector's
    prefetch are not counted."""
    from scripts.paged_rehearse import param_relayout_bytes

    text = """HloModule jit_decode_step_paged_sampled

%fused_computation.1 (p: bf16[48,2048,6144]) -> bf16[48,2048,6144] {
  %p = bf16[48,2048,6144]{2,1,0} parameter(0)
  ROOT %copy.9 = bf16[48,2048,6144]{1,2,0} copy(%p)
}

%layer_body (c: (bf16[1,1536,64,192])) -> (bf16[1,1536,64,192]) {
  %c = (bf16[1,1536,64,192]{3,2,1,0}) parameter(0)
  %w = bf16[1,1536,64,192]{3,2,1,0} get-tuple-element(%c), index=0
  %copy.5 = bf16[1,1536,64,192]{1,3,2,0} copy(%w)
  ROOT %t = (bf16[1,1536,64,192]{3,2,1,0}) tuple(%copy.5)
}

ENTRY %main.7 (params__w_qkv__.1: bf16[48,2048,3,16,128], kv__k__.1: bf16[192,320,16,2048], params__ln_f_w__.1: bf16[2048], params__lead_w_uq__.1: bf16[1,1536,64,192]) -> bf16[4] {
  %params__w_qkv__.1 = bf16[48,2048,3,16,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %kv__k__.1 = bf16[192,320,16,2048]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  %params__ln_f_w__.1 = bf16[2048]{0:T(1024)(128)(2,1)} parameter(2)
  %params__lead_w_uq__.1 = bf16[1,1536,64,192]{3,2,1,0:T(8,128)(2,1)} parameter(3)
  %copy.7 = bf16[48,2048,3,16,128]{4,1,3,2,0:T(8,128)(2,1)} copy(%params__w_qkv__.1), backend_config={"flag_configs":[]}
  %copy.8 = bf16[192,320,16,2048]{2,3,1,0:T(8,128)(2,1)} copy(%kv__k__.1)
  %copy-start.10 = (bf16[2048]{0:T(1024)(128)(2,1)S(1)}, bf16[2048]{0:T(1024)(128)(2,1)}, u32[]{:S(2)}) copy-start(%params__ln_f_w__.1)
  %bitcast.3 = bf16[1536,64,192]{2,1,0:T(8,128)(2,1)} bitcast(%params__lead_w_uq__.1)
  %copy-start.1 = (bf16[1536,64,192]{0,2,1:T(8,128)(2,1)S(1)}, bf16[1536,64,192]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%bitcast.3), cross_program_prefetch_index=0
  %fusion.2 = bf16[4,2048]{1,0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1
  %transpose.4 = bf16[2048,4]{1,0} transpose(%fusion.2), dimensions={1,0}
  ROOT %r = bf16[4]{0} constant(0)
}
"""
    stack, prefetch = 48 * 2048 * 3 * 16 * 128 * 2, 1536 * 64 * 192 * 2
    assert param_relayout_bytes(text) == stack + prefetch
    assert param_relayout_bytes(text, min_bytes=0) == stack + prefetch + 2048 * 2
    held = text.replace("copy(%params__w_qkv__.1)", "copy(%fusion.2)")
    assert param_relayout_bytes(held) == prefetch
