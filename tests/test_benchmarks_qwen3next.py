"""The `qwen3-next-80b-a3b.longctx-rag` cell's rehearsal on the CPU through the
serving runner, as the driver's command runs it; every number of the configuration
file's `reduced_why` held to its architecture module, the initialised tree and the
built pool at the published sizes; the files `load_cell` finds; its traffic file;
the accepted metric files it reads the delta rule's counters through."""

import os
import time

import pytest

from benchmarks import harness, readers, selfcheck, traffic
from benchmarks.arch import qwen3next as arch
from benchmarks.runners import serve as serve_runner

CELL = "qwen3-next-80b-a3b.longctx-rag"
CONFIG = "benchmarks/configs/qwen3-next-80b-a3b.json"
# `BENCHMARK.json` holds 128 per-layer metrics, the most it may, so the cell brings
# no metric of its own: the delta rule's state bytes a step, its masked tokens and
# its slots are read through the accepted files over the counters every model with
# state a sequence books (`KVLayout.state`), the cell appended to their lists.
STATE_METRICS = ("ssm_state_mb_step", "ssm_masked_token_share", "state_slot_util_share")


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 58, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_counts_state_slots_held_experts_and_delta_tokens(obs):
    """The counters are the window's deltas of two snapshots taken WHILE the step
    thread books a program, so an identity between two of them holds up to one
    program at either end (`tests/test_benchmarks_nemotron_h.py`)."""
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c, m = obs["counters"], obs["facts"]["model"]
    opts = obs["facts"]["engine_options"]
    assert c["state_slots_claimed"] >= c["total_finished"] > 0 and c["prefix_hits"] == 0
    assert abs(c["ssm_tokens_scanned"] - c["prefill_tokens_padded"]
               - c["decode_bucket_lanes"]) <= 2 * opts["max_step_tokens"]
    assert 0 < c["ssm_tokens_masked"] < c["ssm_tokens_scanned"]
    assert abs(c["ssm_state_bytes"] - 2 * c["decode_lanes"] * arch.state_bytes(m)) <= \
        2 * 2 * opts["max_num_seqs"] * arch.state_bytes(m)
    assert 0 < c["moe_assign_held"] < c["moe_assign_total"]         # 4 of 8 experts held
    assert 20 < readers.read("moe_held_assign_share", obs) < 80
    assert c["moe_tokens_grouped"] == c["moe_tokens_expert"] > 0
    for name in (*STATE_METRICS, "moe_experts_touched_mean_books", "moe_expert_load_max_books",
                 "decode_lanes_mean_books", "engine_step_ms_books", "kv_util_mean_books",
                 "prefill_token_fill_share", "attn_keys_run_share", "moe_grouped_token_share"):
        assert readers.read(name, obs) > 0, name


def test_weight_bytes_and_pool_bytes_equal_the_hand_count(obs):
    m = obs["facts"]["model"]
    # tiny preset: 8 layers, E 64, F 32, 4 / 2 heads of 32, 2 key heads and 4 value
    # heads of 16, 4 taps, 4 of 8 experts held, 500 rows
    delta = 64 * (128 + 64) + 64 * 8 + 128 * 4 + 8 + 16 + 64 * 64
    attn = 64 * 256 + 2 * 64 * 64 + 128 * 64 + 64
    mlp = lambda held: held * 3 * 64 * 32 + 3 * 64 * 32 + 64 * 8 + 64 + 128
    assert sum(arch.delta_params(m).values()) == delta
    assert sum(arch.attention_params(m).values()) == attn
    assert sum(arch.mlp_params(m, 4).values()) == mlp(4) and arch.layers(m) == {"delta": 6, "attention": 2}
    tree = lambda held: 6 * delta + 2 * attn + 8 * mlp(held) + 2 * 500 * 64 + 64
    assert arch.tree_params(m) == tree(4)
    assert arch.weight_bytes(m) == 2 * (tree(0) - 500 * 64)     # no routed expert, no embedding
    assert arch.kv_block_bytes(m, 8) == 2 * 2 * 2 * 32 * 8 * 2
    assert obs["facts"]["kv_pool_bytes"] == 64 * arch.kv_block_bytes(m, 8)
    assert arch.state_bytes(m) == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)


def test_the_accepted_state_metrics_read_the_delta_rules_counters():
    specs = {"ssm_state_mb_step": ("ssm_state_bytes", "steps_decode"),
             "ssm_masked_token_share": ("ssm_tokens_masked", "ssm_tokens_scanned"),
             "state_slot_util_share": ("state_slot_held_ns", "state_slot_cap_ns")}
    assert set(specs) == set(STATE_METRICS)
    for name, (num, den) in specs.items():
        spec = readers.reader_spec(name)
        assert (spec["kind"], spec["num"], spec["den"]) == ("counter_ratio", num, den)
    canned = {"counters": {"ssm_state_bytes": 2_575_564_800, "steps_decode": 10,
                           "ssm_tokens_masked": 25, "ssm_tokens_scanned": 1000}}
    assert abs(readers.read("ssm_state_mb_step", canned) - 257.55648) < 1e-9
    assert readers.read("ssm_masked_token_share", canned) == 2.5
    assert readers.read("state_slot_util_share", canned) is None    # nothing to read: no error


def test_the_metric_files_pass_the_benchmarks_selfcheck():
    selfcheck.check_files()
    selfcheck.check_arch()


# (what, the architecture module's number, the number `reduced_why` states)
_M = arch.dims(harness.load_json(harness.ROOT, CONFIG), False)
ARITHMETIC = [
    ("a routed expert", arch.mlp_params(_M, 1)["routed"], 3_145_728),
    ("the shared expert", arch.mlp_params(_M, 0)["shared"], 3_145_728),
    ("the router", arch.mlp_params(_M, 0)["router"], 1_048_576),
    ("the shared expert's gate", arch.mlp_params(_M, 0)["shared_gate"], 2_048),
    ("a layer's MLP", sum(arch.mlp_params(_M, 128).values()) - 4_096, 406_849_536),
    ("two norms", arch.mlp_params(_M, 0)["norms"], 4_096),
    ("in_proj_qkvz", arch.delta_params(_M)["in_proj_qkvz"], 25_165_824),
    ("in_proj_ba", arch.delta_params(_M)["in_proj_ba"], 131_072),
    ("the convolution", arch.delta_params(_M)["conv"], 32_768),
    ("A_log and dt_bias", arch.delta_params(_M)["heads"], 64),
    ("the gated norm", arch.delta_params(_M)["gated_norm"], 128),
    ("out_proj", arch.delta_params(_M)["out_proj"], 8_388_608),
    ("a delta mixer", sum(arch.delta_params(_M).values()), 33_718_464),
    ("q and gate", arch.attention_params(_M)["q_gate"], 16_777_216),
    ("k or v", arch.attention_params(_M)["kv"] // 2, 1_048_576),
    ("an attention mixer", sum(arch.attention_params(_M).values()), 27_263_488),
    ("a delta layer", sum(arch.delta_params(_M).values()) + sum(arch.mlp_params(_M, 128).values()),
     440_572_096),
    ("an attention layer",
     sum(arch.attention_params(_M).values()) + sum(arch.mlp_params(_M, 128).values()), 434_117_120),
    ("embedding and head", 2 * _M["vocab_size"] * _M["d_model"], 155_582_464),
    ("the tree", arch.tree_params(_M), 3_667_251_328),
    ("the tree's bytes", arch.tree_params(_M) * arch.BYTES_PER_PARAM, 7_334_502_656),
    ("state a sequence", arch.state_bytes(_M), 12_877_824),
    ("rows a token", arch.kv_block_bytes(_M, 1), 4_096),
    ("layers", tuple(arch.layers(_M).values()), (6, 2)),
    ("the convolution's channels", arch.conv_width(_M), 8_192),
]


@pytest.mark.parametrize("what,got,stated", ARITHMETIC, ids=[a[0] for a in ARITHMETIC])
def test_a_number_of_reduced_why_is_the_architecture_modules(what, got, stated):
    assert got == stated
    if isinstance(stated, int) and stated > 100_000:     # and the file says it, digit for digit
        assert f"{stated:,}" in harness.load_json(harness.ROOT, CONFIG)["reduced_why"], what


def test_published_sizes_give_a_built_tree_pool_and_state_of_exactly_those_bytes():
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, init_params, kv_layout

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    assert (m["n_layers"], m["interval"], m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["d_head"], m["rotary_dim"], m["rope_theta"]) == (8, 4, 2048, 16, 2, 256, 64, 1e7)
    assert (m["key_heads"], m["key_dim"], m["value_heads"], m["value_dim"], m["d_conv"],
            m["chunk"]) == (16, 128, 32, 128, 4, 64)
    assert (m["d_expert"], m["n_experts"], m["top_k"], m["held_start"], m["held_count"]) == \
        (512, 512, 10, 0, 128)
    assert (m["vocab_size"], m["max_seq"], m["norm_eps"]) == (37984, 33792, 1e-6)
    opts = config["runners"]["requests"]["engine_options"]
    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    assert sum(a.size for a in tree.values()) == arch.tree_params(m) == cfg.n_params
    by_kind = lambda prefix: sum(a.size for k, a in tree.items() if k.startswith(prefix))
    assert by_kind("gdn_") == 6 * sum(arch.delta_params(m).values())
    assert by_kind("ga_") == 2 * sum(arch.attention_params(m).values())
    assert by_kind("moe_") + by_kind("shared_") + by_kind("ln1_") + by_kind("ln2_") == \
        8 * sum(arch.mlp_params(m, 128).values())
    assert tree["moe_w_in"].shape == tree["moe_w_gate"].shape == (8, 128, 2048, 512)
    assert tree["moe_router"].shape == (8, 2048, 512) and tree["shared_gate"].shape == (8, 2048)
    assert tree["gdn_w_qkvz"].shape == (6, 2048, 12288) and tree["gdn_w_ba"].shape == (6, 2048, 64)
    assert tree["gdn_conv_w"].shape == (6, 4, 8192) and tree["ga_w_q"].shape == (2, 2048, 16, 512)
    assert tree["tok_embed"].shape == (37984, 2048) and tree["lm_head"].shape == (2048, 37984)
    # a decode step's floor: everything but the routed experts and the embedding's rows
    assert arch.weight_bytes(m) == 2 * (arch.tree_params(m, 0) - 37984 * 2048)
    lay = kv_layout(cfg)
    assert lay.block_bytes(opts["block_size"], 2) == arch.kv_block_bytes(m, opts["block_size"])
    assert lay.state_bytes == arch.state_bytes(m) and (lay.state_layers, lay.depth) == (6, 2)
    pool = jax.eval_shape(lambda: init_paged_cache(
        cfg, opts["num_blocks"], opts["block_size"], opts["max_num_seqs"]))
    assert pool["k"].shape == pool["v"].shape == (2, opts["num_blocks"], opts["block_size"], 512)
    slots = opts["max_num_seqs"] + 1
    assert pool["state"]["gdn"].shape == (6, slots, 32, 128, 128)
    assert pool["state"]["gdn"].dtype == jax.numpy.float32
    assert pool["state"]["conv"].shape == (6, slots, 3 * 8192)
    state = sum(a.size * a.dtype.itemsize for a in pool["state"].values())
    assert state == slots * arch.state_bytes(m)
    assert opts["num_blocks"] * opts["block_size"] == 524_288       # 2 GiB at 4 KiB a token
    assert arch.train_flops_per_token(m, 1) > 0
    assert arch.kernel_costs(m, 64, 1, 1) == {}      # no kernel of its own


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    loaded = harness.load_cell(CELL)
    assert loaded["config"]["arch"] == "qwen3next" and loaded["traffic"]["kind"] == "requests"
    bench = harness.benchmark()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["per_layer"]) == 128       # the cap: no metric of the cell's own
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3-next-80b-a3b")
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-rag-steady"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["file"] == CONFIG and entry["source"] == loaded["config"]["source"] == \
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    assert {"setup_s", "itl_p90_ms"} <= set(e2e) <= {"setup_s", "itl_p90_ms", "ttft_mean_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    split = ".itl" if "ttft_mean_ms" not in e2e else ""
    assert {"ssm_state_mb_step", "ssm_masked_token_share" + split, "state_slot_util_share" + split,
            "moe_held_assign_share", "decode_hbm_roofline", "decode_width_fixed_share",
            "setup_attach_s"} <= set(layer)
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
        assert per_layer[name].get("workloads", [CELL])[-1] == CELL, name     # appended
    # every key of the catalog's row, under its own name; three reduced
    config = loaded["config"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == \
        (8, 128, 37984)
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    assert config["reduced"] == entry["reduced"]
    dep = config["deployment"]
    assert (dep["chips"], dep["pipeline_stages"], dep["chips_sharing_a_layer"], dep["stage"],
            dep["router_experts"], dep["held_experts_start"]) == (24, 6, 4, 0, 512, 0)
    assert set(config["assumed"]) >= {"state_dtype", "qkvz_layout", "no_mtp", "served_positions",
                                      "weights"}
    opts = config["runners"]["requests"]["engine_options"]
    assert set(opts) == {"block_size", "max_num_seqs", "num_blocks", "prefill_chunk_tokens",
                         "max_step_tokens", "host_kv_bytes"}
    whys = config["runners"]["requests"]
    assert all(name in whys for name in (
        "engine_options_why", "max_num_seqs_why", "block_size_why", "num_blocks_why",
        "prefill_chunk_why", "host_kv_bytes_why", "token_check_why", "token_tolerance_why"))
    check, chunk = whys["token_check"], opts["prefill_chunk_tokens"]
    assert check["prompt_len"] > 2 * chunk and check["prompt_len"] % chunk  # a padded third chunk
    assert check["prompt_len"] % dep["delta_chunk"]                        # inside a delta chunk


def test_the_traffic_file_is_the_issues_and_its_schedule_is_the_same_for_two_seeds():
    mix = harness.load_json(harness.HERE, "traffic", "longctx-rag-steady.json")
    assert mix["kind"] == "requests" and mix["sharing"] is None and mix["max_total"] == 33792
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192, "sigma": 0.8,
                                 "min": 1024, "max": 32768}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                                 "min": 32, "max": 1024}
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert abs(knee["rate_rps"] / knee["knee_rps"] - 0.8) < 1e-9 and knee["sweeps"]
    a = traffic.requests(mix, 4000000001, 45.0, 37984)
    b = traffic.requests(mix, 4000000002, 45.0, 37984)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_rps"] * 45)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(1024 <= len(r.prompt) <= 32768 and 1 <= r.max_new_tokens <= 1024
               and len(r.prompt) + r.max_new_tokens <= 33792 for r in a)
    assert max(max(r.prompt) for r in a) < 37984        # ids of the held slice


def test_program_refuses_a_checkout_without_the_model(monkeypatch):
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    monkeypatch.setattr(gpt, "CONFIGS", {k: v for k, v in gpt.CONFIGS.items()
                                         if k != "qwen3-next-80b-a3b"})
    with pytest.raises(SystemExit, match="no model 'qwen3-next-80b-a3b'"):
        arch.program(config, m)
    assert all(callable(getattr(arch, name)) for name in harness.ARCH_INTERFACE)
