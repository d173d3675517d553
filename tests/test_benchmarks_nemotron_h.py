"""The `nemotron3-nano-30b-a3b.agent-reason` cell's rehearsal on the CPU through
the serving runner, as the driver's command runs it; every number of the
configuration file's `reduced_why` held to its architecture module, the
initialised tree and the built pool at the published sizes; the files
`load_cell` finds; its traffic file; its metric files over what a run observed."""

import os
import time

import pytest

from benchmarks import harness, readers, selfcheck, traffic
from benchmarks.arch import nemotron_h as arch
from benchmarks.runners import serve as serve_runner

CELL = "nemotron3-nano-30b-a3b.agent-reason"
CONFIG = "benchmarks/configs/nemotron3-nano-30b-a3b.json"
NEW_METRICS = {
    "ssd_state_mb_step": {"kind": "counter_ratio", "num": "ssm_state_bytes",
                          "den": "steps_decode", "scale": 1e-6},
    "ssd_block_share": {"kind": "counter_ratio", "num": "blocks_ssm", "den": "blocks_run",
                        "scale": 100.0},
    "moe_block_share": {"kind": "counter_ratio", "num": "blocks_moe", "den": "blocks_run",
                        "scale": 100.0},
    # the decode step's state update (gather, advance, scatter) and its read-out y = S C, by
    # the names the chip's trace prints for them (PERF.md §5: the chunk form's products
    # are plain `fusion`s and are not in it)
    "ssd_time_share": {"kind": "trace_op_share",
                       "ops": ["multiply_reduce_fusion", "bitcast_dynamic-update-slice_fusion"]},
}
# The cell reports NO end-to-end `ttft_mean_ms` (its runs spread 5-7% against the 3.5% the
# driver admits: PERF.md §6, second session), so what would move it is read under the
# `.itl` names, as `ouro-2.6b.reason` reads it since PR 32: two that this PR adds over the
# accepted readers' files (a dotted name reads the file of its first part) ...
SPLIT = ("state_slot_util_share.itl", "ssm_masked_token_share.itl")
# ... and the accepted lists the cell joins. `moe_experts_touched_mean` and
# `moe_expert_load_max` read the step spans' args, which no traced run of the cell reported
# on the chip (as eight span metrics of `jamba2-3b.chat-burst` do not: PERF.md §7): the
# cell is left off their lists
JOINED = ("moe_held_assign_share", "moe_grouped_time_share",
          "engine_step_ms_books", "engine_wait_share_books", "step_build_ms_books",
          "step_fetch_ms_books", "step_export_ms_books", "decode_device_ms",
          "prefill_device_ms.itl", "ttft_mean_ms.itl", "serve_idle_share", "setup_weights_s",
          "setup_deploy_s", "setup_warm_s")


# ... and, since PR 53, the gauges' integer books, which need no span to arrive
BOOKS_53 = ("kv_util_mean_books.itl", "decode_lanes_mean_books",
            "moe_experts_touched_mean_books", "moe_expert_load_max_books",
            "step_between_ms", "flight_drop_share")
# ... and what later PRs listed every serving cell in (PR 57)
LATER = {"decode_width_fixed_share"}


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 51, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_counts_state_slots_experts_and_blocks(obs):
    """The counters are the window's deltas of two snapshots that another thread takes
    WHILE the step thread books a program (the window closes on requests in flight), so
    an identity between two counters holds up to ONE program at either end: the
    rehearsal's step budget (48 tokens, 8 lanes). Held exactly it failed one run in
    some under the driver's six workers (PR 57's run) and never alone."""
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c, m = obs["counters"], obs["facts"]["model"]
    opts = obs["facts"]["engine_options"]
    program = 2 * opts["max_step_tokens"]        # a program's tokens, at both ends
    assert c["state_slots_claimed"] >= c["total_finished"] > 0 and c["prefix_hits"] == 0
    assert abs(c["ssm_tokens_scanned"]
               - c["prefill_tokens_padded"] - c["decode_bucket_lanes"]) <= program
    assert abs(c["ssm_state_bytes"] - 2 * c["decode_lanes"] * arch.state_bytes(m)) <= \
        2 * 2 * opts["max_num_seqs"] * arch.state_bytes(m)
    # the rehearsal's pattern MEM*E: two Mamba-2 blocks, two expert blocks, one attention
    run = c["blocks_run"]
    assert run > 500 and all(abs(5 * c["blocks_" + kind] - n * run) <= 2 * 5 * 5
                             for kind, n in (("ssm", 2), ("moe", 2), ("attn", 1)))
    assert 0 < c["moe_assign_held"] < c["moe_assign_total"]         # 4 of 8 experts held
    assert readers.read("ssd_state_mb_step", obs) == 1e-6 * c["ssm_state_bytes"] / c["steps_decode"]
    assert abs(readers.read("ssd_block_share", obs) - 40.0) < 0.5
    assert abs(readers.read("moe_block_share", obs) - 40.0) < 0.5
    # the gauges by their integer books (PR 53): a span that has not reached the
    # controller a second after the window reads as nothing, the books never
    for name in ("state_slot_util_share", "ssm_masked_token_share", "moe_held_assign_share",
                 "moe_experts_touched_mean_books", "moe_expert_load_max_books",
                 "engine_step_ms_books", "step_build_ms_books", "step_fetch_ms_books",
                 "step_export_ms_books"):
        assert readers.read(name, obs) > 0, name
    assert 20 < readers.read("moe_held_assign_share", obs) < 80


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_metric_file_reads_a_canned_observation_and_nothing_from_the_parent(name):
    spec = NEW_METRICS[name]
    assert readers.reader_spec(name) == spec and spec["kind"] in readers.KINDS
    canned = {"counters": {"ssm_state_bytes": 50_000_000, "steps_decode": 10,
                           "blocks_run": 130, "blocks_ssm": 60, "blocks_moe": 50},
              "trace": {"busy_s": 4.0, "window_s": 5.0, "op_self_s": {
                  "multiply_reduce_fusion": 0.5, "bitcast_dynamic-update-slice_fusion": 0.5,
                  "fusion": 2.0}}}
    want = {"ssd_state_mb_step": 5.0, "ssd_block_share": 100 * 60 / 130,
            "moe_block_share": 100 * 50 / 130, "ssd_time_share": 25.0}[name]
    assert abs(readers.read(name, canned) - want) < 1e-9
    # a program without the counters (the parent's) is read as nothing, not an error
    assert readers.read(name, {"counters": {"decode_lanes": 3}, "trace": None}) is None
    # a model without such blocks: the counters are there and read 0
    zeros = {"counters": dict.fromkeys(canned["counters"], 0), "trace": None}
    assert readers.read(name, zeros) is None


def test_the_metric_files_pass_the_benchmarks_selfcheck():
    selfcheck.check_files()
    selfcheck.check_arch()


# (what, the architecture module's number, the number `reduced_why` states)
_M = arch.dims(harness.load_json(harness.ROOT, CONFIG), False)
ARITHMETIC = [
    ("a routed expert", arch.expert_params(_M, 1)["routed"], 9_977_856),
    ("the shared expert", arch.expert_params(_M, 0)["shared"], 19_955_712),
    ("the router", arch.expert_params(_M, 0)["router"], 344_064),
    ("the selection bias", arch.expert_params(_M, 0)["select_bias"], 128),
    ("an E block", sum(arch.expert_params(_M, 64).values()), 658_885_376),
    ("in_proj", arch.mamba_params(_M)["in_proj"], 27_697_152),
    ("the convolution", arch.mamba_params(_M)["conv"], 30_720),
    ("A_log, D, dt_bias", arch.mamba_params(_M)["heads"], 192),
    ("the gated norm", arch.mamba_params(_M)["gated_norm"], 4_096),
    ("out_proj", arch.mamba_params(_M)["out_proj"], 11_010_048),
    ("an M block", sum(arch.mamba_params(_M).values()), 38_744_896),
    ("q", arch.attention_params(_M)["q"], 11_010_048),
    ("k or v", arch.attention_params(_M)["kv"] // 2, 688_128),
    ("a * block", sum(arch.attention_params(_M).values()), 23_399_040),
    ("the tree", arch.tree_params(_M), 3_926_018_560),
    ("the tree's bytes", arch.tree_params(_M) * arch.BYTES_PER_PARAM, 7_852_037_120),
    ("state a sequence", arch.state_bytes(_M), 12_804_096),
    ("rows a token", arch.kv_block_bytes(_M, 1), 2_048),
    ("blocks", tuple(arch.blocks(_M).values()), (6, 5, 2)),
    ("the convolution's channels", arch.conv_width(_M), 6_144),
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
    assert (m["pattern"], m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == \
        ("MEMEM*EMEMEM*", 2688, 32, 2, 128)
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"], m["d_state"], m["d_conv"],
            m["chunk"]) == (64, 64, 8, 128, 4, 128)
    assert (m["d_expert"], m["d_shared"], m["n_experts"], m["top_k"], m["held_start"],
            m["held_count"], m["route_scale"]) == (1856, 3712, 128, 6, 0, 64, 2.5)
    assert (m["vocab_size"], m["max_seq"], m["norm_eps"]) == (65536, 16384, 1e-5)
    opts = config["runners"]["requests"]["engine_options"]
    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    assert sum(a.size for a in tree.values()) == arch.tree_params(m) == cfg.n_params
    assert tree["moe_w_in"].shape == (5, 64, 1856, 2688)      # out-features first
    assert tree["moe_router"].shape == (5, 2688, 128) and tree["shared_w_in"].shape == (5, 2688, 3712)
    assert tree["m2_w_in"].shape == (6, 2688, 10304) and tree["w_q"].shape == (2, 2688, 32, 128)
    # a decode step's floor: everything but the routed experts and the embedding's rows
    assert arch.weight_bytes(m) == 2 * (arch.tree_params(m, 0) - 65536 * 2688)
    lay = kv_layout(cfg)
    assert lay.block_bytes(opts["block_size"], 2) == arch.kv_block_bytes(m, opts["block_size"])
    assert lay.state_bytes == arch.state_bytes(m) and lay.state_layers == 6
    assert dict(lay.kinds) == {"run": 13, "ssm": 6, "moe": 5, "attn": 2}
    pool = jax.eval_shape(lambda: init_paged_cache(
        cfg, opts["num_blocks"], opts["block_size"], opts["max_num_seqs"]))
    assert pool["k"].shape == pool["v"].shape == (2, opts["num_blocks"], opts["block_size"], 256)
    assert pool["state"]["ssm"].shape == (6, 65, 64, 64, 128)
    assert pool["state"]["conv"].shape == (6, 65, 3 * 6144)
    state = sum(a.size * a.dtype.itemsize for a in pool["state"].values())
    assert state == (opts["max_num_seqs"] + 1) * arch.state_bytes(m)
    assert opts["num_blocks"] * opts["block_size"] == 64 * 16384     # 64 lanes at the longest
    assert arch.train_flops_per_token(m, 1) > 0
    costs = arch.kernel_costs(m, 64, 1, 1)
    assert set(costs) == {"moe_grouped_hidden_ungated", "moe_grouped_down"}
    assert all(v["flops"] > 0 and v["bytes"] > 0 for v in costs.values())


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    loaded = harness.load_cell(CELL)
    assert loaded["config"]["arch"] == "nemotron_h" and loaded["traffic"]["kind"] == "requests"
    bench = harness.benchmark()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell, entry = bench["workloads"][9], bench["configs"][7]
    assert cell["name"] == CELL and entry["name"] == "nemotron3-nano-30b-a3b"
    assert cell["chips"] == 1 and cell["traffic"] == "agent-reason-steady"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["file"] == CONFIG and entry["source"] == loaded["config"]["source"] == \
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    assert set(e2e) == {"setup_s", "itl_p90_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    assert set(NEW_METRICS) | set(SPLIT) | set(JOINED) | set(BOOKS_53) | {"setup_attach_s"} == set(layer) - LATER
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(NEW_METRICS)))               # appended, in PR 51's order
    assert names[at:at + len(NEW_METRICS) + len(SPLIT)] == list(NEW_METRICS) + list(SPLIT)
    assert set(BOOKS_53) <= set(names[at + len(NEW_METRICS) + len(SPLIT):])    # PR 53's behind them
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "paged model path"
    for name in SPLIT:      # the accepted entry's own words, but for what it moves and where
        base = per_layer[name[:-len(".itl")]]
        assert CELL not in base["workloads"] and base["moves"] == "ttft_mean_ms"
        mine = per_layer[name]
        assert {**mine, "workloads": None} == {**base, "name": name, "moves": "itl_p90_ms",
                                               "workloads": None}
        assert mine["workloads"][0] == CELL         # later cells read them too, behind it
    assert per_layer["ssd_time_share"]["source"] == "device_trace"
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"][1:]          # appended to the list (later cells behind it)
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    # every published key of the catalog's row, under its own name; three reduced
    config = loaded["config"]
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "head_dim": 128, "hidden_size": 2688, "intermediate_size": 1856,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
        "n_group": 1, "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == \
        (13, 64, 65536)
    assert config["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert config["reduced"] == entry["reduced"]
    dep = config["deployment"]
    assert (dep["chips"], dep["pipeline_stages"], dep["chips_sharing_a_layer"], dep["stage"]) == \
        (8, 4, 2, 0)
    assert set(config["assumed"]) >= {"no_rotary", "dt_not_clamped", "gated_norm",
                                      "selection_bias", "rescale_prenorm_residual", "weights"}
    opts = config["runners"]["requests"]["engine_options"]
    assert set(opts) == {"block_size", "max_num_seqs", "num_blocks", "prefill_chunk_tokens",
                         "max_step_tokens", "host_kv_bytes"}
    whys = config["runners"]["requests"]
    assert all(name in whys for name in (
        "engine_options_why", "max_num_seqs_why", "block_size_why", "num_blocks_why",
        "prefill_chunk_why", "host_kv_bytes_why", "token_check_why", "token_tolerance_why"))
    check, chunk = whys["token_check"], opts["prefill_chunk_tokens"]
    assert check["prompt_len"] > 2 * chunk and check["prompt_len"] % chunk  # a padded third chunk
    assert check["prompt_len"] % chunk % config["chunk_size"]               # inside the scan's chunk


def test_the_traffic_file_is_the_issues_and_its_schedule_is_the_same_for_two_seeds():
    mix = harness.load_json(harness.HERE, "traffic", "agent-reason-steady.json")
    assert mix["kind"] == "requests" and mix["sharing"] is None and mix["max_total"] == 16384
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1536, "sigma": 0.9,
                                 "min": 128, "max": 12288}
    assert mix["output_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                                 "min": 64, "max": 2048}
    assert mix["trace"] == {"after_s": 20.0, "seconds": 5.0}
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert knee["rate_rps"] == 0.7 * knee["knee_rps"] and len(knee["sweeps"]) >= 2   # the fallback
    a = traffic.requests(mix, 4000000001, 45.0, 65536)
    b = traffic.requests(mix, 4000000002, 45.0, 65536)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_rps"] * 45)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(128 <= len(r.prompt) <= 12288 and r.max_new_tokens <= 2048
               and len(r.prompt) + r.max_new_tokens <= 16384 for r in a)
    assert max(max(r.prompt) for r in a) < 65536        # ids of the held slice


def test_program_refuses_a_checkout_without_the_model(monkeypatch):
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    monkeypatch.setattr(gpt, "CONFIGS", {k: v for k, v in gpt.CONFIGS.items()
                                         if k != "nemotron3-nano-30b-a3b"})
    with pytest.raises(SystemExit, match="no model 'nemotron3-nano-30b-a3b'"):
        arch.program(config, m)
    assert all(callable(getattr(arch, name)) for name in harness.ARCH_INTERFACE)
