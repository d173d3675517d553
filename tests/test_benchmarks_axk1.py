"""The `ax-k1.longdoc` cell's rehearsal on the CPU through the serving runner,
as the driver's command runs it (`benchmarks.runners.serve.run`), and the
arithmetic of its architecture module against a hand count, a built tree and
a built pool."""

import json
import os
import time

import pytest

from benchmarks import harness, readers
from benchmarks.arch import axk1 as arch
from benchmarks.runners import serve as serve_runner
from scripts.axk1_tolerance import WRONG

CELL = "ax-k1.longdoc"
CONFIG = "benchmarks/configs/ax-k1.json"


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 34, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_counts_the_assignments_that_fell_here(obs):
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c = obs["counters"]
    steps = [ev["args"] for ev in obs["spans"] if ev["name"] == "engine.step"]
    decodes = [a for a in steps if a["decodes"]]
    # tiny preset: top-3 of 16 with 4 held, two expert layers
    assert decodes and all(
        a["assign_total"] == 2 * 3 * a["decodes"] and 0 <= a["assign_held"] <= a["assign_total"]
        and 0 <= a["experts_touched"] <= 4 for a in decodes)
    assert 0 < c["moe_assign_held"] < c["moe_assign_total"]
    share = readers.read("moe_held_assign_share", obs)
    assert share == 100.0 * c["moe_assign_held"] / c["moe_assign_total"]
    assert 10.0 < share < 45.0                      # 4 of 16: a quarter in the mean
    # every program, chunk or decode step, takes the one served form
    assert 0 < c["moe_tokens_grouped"] == c["moe_tokens_expert"]
    assert readers.read("moe_grouped_token_share", obs) == 100.0
    # two expert layers a decode step; top-3 of 16 with 4 held leaves a lone
    # lane's layer empty 4 times in 10
    assert c["moe_layers_routed"] == 2 * len(decodes)
    assert 0 <= c["moe_layers_empty"] <= c["moe_layers_routed"] - (c["moe_assign_held"] > 0)
    empty = readers.read("moe_empty_layer_share", obs)
    assert empty == 100.0 * c["moe_layers_empty"] / c["moe_layers_routed"]
    # a program without the counter (the parent's) is read as nothing, not an error
    assert readers.read("moe_empty_layer_share", {"counters": {
        k: v for k, v in c.items() if not k.startswith("moe_layers")}}) is None
    for name in ("kv_util_mean", "prefill_span_p90_ms", "queue_wait_p50_ms",
                 "decode_lanes_mean", "engine_step_ms", "moe_experts_touched_mean",
                 "moe_expert_load_max", "attn_keys_run_share", "decode_chained_share"):
        assert readers.read(name, obs) > 0, name


def test_weight_bytes_and_pool_bytes_equal_the_hand_count(obs):
    m = obs["facts"]["model"]
    # attention: q_a 64x24, q_b 24x4x24, kv_a 64x40, kv_b 32x4x32, o 64x64
    attn = 64 * 24 + 24 * 96 + 64 * 40 + 32 * 128 + 64 * 64
    assert arch.attention_params(m) == attn
    # outside the routed experts: the dense layer (MLP 3 x 64 x 96), two expert
    # layers (shared expert 3 x 64 x 32, router 64 x 16), the head 64 x 500
    outside = attn + 3 * 64 * 96 + 2 * (attn + 3 * 64 * 32 + 64 * 16) + 64 * 500
    assert arch.weight_bytes(m) == 2 * outside
    assert arch.held_params(m) == outside + 2 * 4 * 3 * 64 * 32 + 64 * 500
    # one latent row of 32 + 8 in a whole tile of 128, 3 layers, 8 tokens, bf16
    assert arch.kv_block_bytes(m, 8) == 3 * 128 * 8 * 2
    assert obs["facts"]["kv_pool_bytes"] == 128 * 3 * 128 * 8 * 2
    assert arch.kernel_costs(m, 1, 1, 1) == {}


def test_published_sizes_give_the_issues_bytes_and_a_built_tree_and_pool():
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, init_params, kv_layout

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    assert (m["d_model"], m["n_heads"], m["q_lora"], m["kv_lora"]) == (7168, 64, 1536, 512)
    assert (m["d_nope"], m["d_rope"], m["d_v"], m["d_dense"], m["d_expert"]) == \
        (128, 64, 128, 18432, 2048)
    assert (m["n_experts"], m["top_k"], m["n_shared"], m["route_scale"]) == (192, 8, 1, 2.5)
    assert (m["n_layers"], m["dense_layers"], m["held_count"], m["vocab_size"]) == \
        (7, 1, 12, 20480)
    assert config["max_position_embeddings"] == 131072 and m["max_seq"] == 16384
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 192, "vocab_size": 163840}
    assert set(config["assumed"]) >= {
        "topk_method", "rotary_layout", "latent_norms", "router_input", "ep_size", "weights"}
    # the issue's arithmetic: attention 101.12 M, outside the routed experts
    # 146.55 M a layer, an expert 44.04 M, an expert layer here 675.0 M, the
    # dense layer 497.5 M, 9.68 GB in all
    assert arch.attention_params(m) == 101_122_048
    assert arch.layer_params(m, 0) == 146_538_496 and arch.layer_params(m, 12) == 675_020_800
    assert arch.dense_layer_params(m) == 497_483_776
    assert arch.held_params(m) == 497_483_776 + 6 * 675_020_800 + 2 * 146_800_640
    assert 9.67e9 < 2 * arch.held_params(m) < 9.69e9
    # a decode step streams at least everything outside the routed experts: 3.05 GB
    assert arch.weight_bytes(m) == 2 * (497_483_776 + 6 * 146_538_496 + 146_800_640)
    assert 3.04e9 < arch.weight_bytes(m) < 3.06e9
    opts = config["runners"]["requests"]["engine_options"]
    assert arch.kv_block_bytes(m, opts["block_size"]) == 7 * 640 * 64 * 2
    # the program's own tree and pool at these sizes
    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    matrices = sum(a.size for k, a in tree.items() if a.ndim >= 2 and "norm" not in k
                   and not k.split("lead_")[-1].startswith(("b_", "ln")))
    assert matrices == arch.held_params(m)
    assert cfg.n_params - matrices < 1e6            # the norms' weights
    pool = jax.eval_shape(lambda: init_paged_cache(cfg, opts["num_blocks"], opts["block_size"]))
    assert set(pool) == {"k"} and pool["k"].shape == (7, 4096, 64, 640)
    assert pool["k"].size * 2 == opts["num_blocks"] * arch.kv_block_bytes(m, opts["block_size"])
    assert kv_layout(cfg).block_bytes(64, 2) == arch.kv_block_bytes(m, 64)
    assert arch.train_flops_per_token(m, 1) > 6 * arch.weight_bytes(m) / 2


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    bench = harness.benchmark()
    assert len(bench["workloads"]) >= 7 and len(bench["configs"]) >= 5   # later PRs append
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][6]["name"] == CELL and bench["configs"][4]["name"] == "ax-k1"
    cell = bench["workloads"][6]
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-steady" and len(cell["why"]) <= 200
    entry = bench["configs"][4]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["file"] == CONFIG and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    assert {"setup_s", "itl_p90_ms"} <= set(e2e) <= {"setup_s", "itl_p90_ms", "ttft_mean_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    assert {"moe_held_assign_share", "moe_experts_touched_mean", "moe_expert_load_max",
            "decode_hbm_roofline", "decode_device_ms", "decode_chained_share",
            "setup_weights_s", "serve_idle_share", "compiles_in_window"} <= set(layer)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    names = [m["name"] for m in bench["per_layer"]]     # later PRs append
    assert names.index("moe_held_assign_share") < names.index("moe_grouped_token_share")
    assert per_layer["moe_grouped_token_share"]["workloads"][:2] == [
        "smallthinker-21b-a3b.mixed-len", CELL]
    assert per_layer["moe_grouped_token_share"]["moves"] == "ttft_mean_ms"
    assert readers.reader_spec("moe_grouped_token_share") == {
        "kind": "counter_ratio", "num": "moe_tokens_grouped",
        "den": "moe_tokens_expert", "scale": 100.0}
    assert per_layer["moe_held_assign_share"]["workloads"][0] == CELL      # later cells append
    assert names.index("moe_grouped_token_share") < names.index("moe_empty_layer_share")
    assert per_layer["moe_empty_layer_share"] == {
        "name": "moe_empty_layer_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "paged model path",
        "moves": "itl_p90_ms", "workloads": [CELL]}
    assert readers.reader_spec("moe_empty_layer_share") == {
        "kind": "counter_ratio", "num": "moe_layers_empty", "den": "moe_layers_routed",
        "scale": 100.0}
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    assert readers.reader_spec("moe_held_assign_share") == {
        "kind": "counter_ratio", "num": "moe_assign_held", "den": "moe_assign_total",
        "scale": 100.0}
    mix = harness.load_json(harness.HERE, "traffic", "longdoc-steady.json")
    assert mix["sharing"] is None and mix["max_total"] == 16384
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"]) == (6144, 0.6)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (2048, 16000)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"]) == (96, 0.7)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (16, 384)
    assert mix["trace"] == {"after_s": 20.0, "seconds": 5.0}
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert knee["rate_rps"] <= 0.85 * knee["knee_rps"]
    # every published key of the catalog's row, under its own name
    config = harness.load_json(harness.ROOT, CONFIG)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
        "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "none", "v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    opts = config["runners"]["requests"]["engine_options"]
    assert opts == {"block_size": 64, "max_num_seqs": 16, "num_blocks": 4096,
                    "prefill_chunk_tokens": 512, "max_step_tokens": 528, "host_kv_bytes": 0}


def test_program_refuses_a_checkout_without_the_model(monkeypatch):
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    monkeypatch.setattr(gpt, "CONFIGS", {k: v for k, v in gpt.CONFIGS.items() if k != "ax-k1"})
    with pytest.raises(SystemExit, match="no model 'ax-k1'"):
        arch.program(config, m)
    assert all(callable(getattr(arch, name)) for name in harness.ARCH_INTERFACE)


@pytest.fixture(scope="module")
def readings():
    """`scripts.axk1_tolerance` at the tiny preset: every reading is
    `bench_check_tokens` itself, on the engine's own greedy tokens."""
    import contextlib
    import io

    from scripts import axk1_tolerance

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert axk1_tolerance.main(["--rehearse", "--seeds", "3400000001", "--parts", "wrong"]) == 0
    return json.loads(out.getvalue().splitlines()[-1])["rows"][0]


@pytest.mark.parametrize("control", list(WRONG))
def test_the_token_check_itself_fails_each_control(readings, control):
    """The benchmark's own check, not a copy of it: the sound engine inside,
    each wrong reference outside, threefold and more (float32 at the tiny preset)."""
    assert 0 < readings["moe_assign"][0] < readings["moe_assign"][1]
    assert readings["sound"]["token_err"] < 0.005
    assert readings[control]["token_err"] > 0.015     # 16 tokens of a 3-layer model
