"""The `jamba2-3b.chat-burst` cell's rehearsal on the CPU through the serving
runner, as the driver's command runs it (`benchmarks.runners.serve.run`); the
arithmetic of its architecture module against a hand count, the initialised
tree and the built pool at the published sizes; its traffic file; its four
metric files over what a run observed and over a canned trace; and the
benchmark's own token check failing each control at the tiny preset."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import harness, readers, traffic
from benchmarks.arch import jamba as arch
from benchmarks.runners import serve as serve_runner

CELL = "jamba2-3b.chat-burst"
CONFIG = "benchmarks/configs/jamba2-3b.json"
NEW_METRICS = {
    "state_slot_util_share": {"kind": "counter_ratio", "num": "state_slot_held_ns",
                              "den": "state_slot_cap_ns", "scale": 100.0},
    "ssm_state_mb_step": {"kind": "counter_ratio", "num": "ssm_state_bytes",
                          "den": "steps_decode", "scale": 1e-6},
    "ssm_masked_token_share": {"kind": "counter_ratio", "num": "ssm_tokens_masked",
                               "den": "ssm_tokens_scanned", "scale": 100.0},
    "ssm_scan_time_share": {"kind": "trace_op_share", "ops": ["ssm_scan"]},
}


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 40, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_counts_slots_state_and_masked_tokens(obs):
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c = obs["counters"]
    # every request of the window claimed a slot and gave it back, or holds it
    assert c["state_slots_claimed"] >= c["total_finished"] > 0
    assert 0 <= c["state_slots_claimed"] - c["state_slots_released"] <= 8
    assert c["prefix_hits"] == 0
    assert c["ssm_tokens_scanned"] == c["prefill_tokens_padded"] + c["decode_bucket_lanes"]
    assert c["ssm_tokens_masked"] == (c["prefill_tokens_padded"] - c["prefill_tokens"]
                                      + c["decode_bucket_lanes"] - c["decode_lanes"])
    m = obs["facts"]["model"]
    assert c["ssm_state_bytes"] == 2 * c["decode_lanes"] * arch.state_bytes(m)
    assert 0 < c["state_slot_held_ns"] < c["state_slot_cap_ns"]
    util = readers.read("state_slot_util_share", obs)
    assert util == 100.0 * c["state_slot_held_ns"] / c["state_slot_cap_ns"] and 0 < util < 100
    assert readers.read("ssm_state_mb_step", obs) == \
        1e-6 * c["ssm_state_bytes"] / c["steps_decode"]
    assert 0 < readers.read("ssm_masked_token_share", obs) < 60
    assert readers.read("ssm_scan_time_share", obs) is None      # no device trace here
    for name in ("kv_util_mean", "queue_wait_p50_ms", "decode_lanes_mean", "engine_step_ms",
                 "attn_keys_run_share", "decode_chained_share", "prefill_token_fill_share",
                 "decode_bucket_fill_share", "stream_send_ms"):
        assert readers.read(name, obs) > 0, name


def test_the_four_metric_files_read_a_canned_observation():
    for name, spec in NEW_METRICS.items():
        assert readers.reader_spec(name) == spec and spec["kind"] in readers.KINDS
    canned = {
        "counters": {"state_slot_held_ns": 3_000, "state_slot_cap_ns": 12_000,
                     "ssm_state_bytes": 50_000_000, "steps_decode": 10,
                     "ssm_tokens_masked": 25, "ssm_tokens_scanned": 100},
        "trace": {"busy_s": 2.0, "window_s": 5.0,
                  "op_self_s": {"ssm_scan": 0.3, "ssm_scan.clone": 0.1, "fusion": 1.6}},
    }
    assert readers.read("state_slot_util_share", canned) == 25.0
    assert readers.read("ssm_state_mb_step", canned) == 5.0
    assert readers.read("ssm_masked_token_share", canned) == 25.0
    assert abs(readers.read("ssm_scan_time_share", canned) - 20.0) < 1e-9
    # a program without the counters (the parent's) is read as nothing, not an error
    parent = {"counters": {"steps_decode": 10}, "trace": None}
    assert all(readers.read(name, parent) is None for name in NEW_METRICS)
    # a model without state: the counters are there and read 0
    plain = {"counters": {"state_slot_held_ns": 0, "state_slot_cap_ns": 0,
                          "ssm_tokens_masked": 0, "ssm_tokens_scanned": 0}}
    assert readers.read("state_slot_util_share", plain) is None
    assert readers.read("ssm_masked_token_share", plain) is None


SERVING = ["gpt2-large.chat", "gpt2-large.chat-sat", "smallthinker-21b-a3b.mixed-len",
           "ouro-2.6b.reason", "ax-k1.longdoc", "jamba2-3b.chat-burst", "laguna-xs2.codegen",
           "nemotron3-nano-30b-a3b.agent-reason", "phi4-mini-flash.long-reason"]


@pytest.mark.parametrize("cell", SERVING)
def test_every_serving_cell_reads_the_decode_tables_one_width_share(cell, obs):
    """PR 57's metric is data alone: a file for the `counter_ratio` reader and one
    entry, the nine serving cells, moving `setup_s`. A parent without the counter
    reads nothing; a CPU rehearsal, whose programs gather by the bucket, reads 0."""
    name = "decode_width_fixed_share"
    assert readers.reader_spec(name) == {"kind": "counter_ratio", "num": "decode_width_fixed",
                                         "den": "decode_dispatched", "scale": 100.0}
    bench = harness.benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert {**entry, "workloads": entry["workloads"][:9]} == {       # a later serving cell appends
        "name": name, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "weights and compile", "moves": "setup_s", "workloads": SERVING}
    assert name in harness.cell_metrics(bench, cell, "per_layer")
    assert "setup_s" in harness.cell_metrics(bench, cell, "end_to_end")
    assert readers.read(name, {"counters": {"decode_width_fixed": 30, "decode_dispatched": 40}}) == 75.0
    assert readers.read(name, {"counters": {"decode_dispatched": 40}}) is None        # the parent
    assert obs["counters"]["decode_dispatched"] > 0 and readers.read(name, obs) == 0.0


def test_weight_bytes_and_pool_bytes_equal_the_hand_count(obs):
    m = obs["facts"]["model"]
    # tiny preset: E 64, Di 128, N 16, R 8, K 4
    mamba = (64 * 256 + 128 * 4 + 128 + 128 * (8 + 32) + 8 + 32 + 8 * 128 + 128
             + 128 * 16 + 128 + 128 * 64)
    attn = 64 * 64 + 2 * 64 * 16 + 64 * 64
    assert arch.mamba_params(m) == mamba and arch.attention_params(m) == attn
    tree = 2 * mamba + 2 * attn + 4 * (3 * 64 * 96 + 128) + 500 * 64 + 64
    assert arch.tree_params(m) == tree and arch.weight_bytes(m) == 2 * tree
    assert arch.kv_block_bytes(m, 8) == 2 * 2 * 16 * 8 * 2
    assert obs["facts"]["kv_pool_bytes"] == 48 * arch.kv_block_bytes(m, 8)
    assert arch.state_bytes(m) == 2 * (128 * 16 * 4 + 128 * 3 * 2)
    assert arch.kernel_costs(m, 2, 8, 1) == {"ssm_scan": {
        "flops": 7.0 * 2 * 8 * 128 * 16,
        "bytes": 4.0 * (3 * 2 * 8 * 128 + 2 * 2 * 8 * 16 + 2 * 2 * 16 * 128 + 16 * 128)}}


def test_published_sizes_give_the_issues_bytes_and_a_built_tree_and_pool():
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, init_params, kv_layout

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == \
        (28, 2560, 20, 1, 128)
    assert (m["d_mlp"], m["d_state"], m["d_conv"], m["expand"], m["dt_rank"]) == \
        (8192, 16, 4, 2, 160)
    assert (m["vocab_size"], m["max_seq"], arch.mamba_layers(m)) == (65536, 262144, 26)
    # the issue's arithmetic, redone in the file's `reduced_why`
    assert arch.mamba_params(m) == 41_241_792 and arch.attention_params(m) == 13_762_560
    assert arch.tree_params(m) == 3_029_337_472
    assert arch.weight_bytes(m) == 6_058_674_944
    assert arch.state_bytes(m) == 9_318_400
    opts = config["runners"]["requests"]["engine_options"]
    assert arch.kv_block_bytes(m, opts["block_size"]) == 131_072      # 1 KiB a token
    # the program's own tree, pool and state at these sizes: EXACTLY those bytes
    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    assert sum(a.size * a.dtype.itemsize for a in tree.values()) == arch.weight_bytes(m)
    assert cfg.n_params == arch.tree_params(m)
    lay = kv_layout(cfg)
    assert lay.block_bytes(opts["block_size"], 2) == arch.kv_block_bytes(m, opts["block_size"])
    assert lay.state_bytes == arch.state_bytes(m)
    pool = jax.eval_shape(lambda: init_paged_cache(
        cfg, opts["num_blocks"], opts["block_size"], opts["max_num_seqs"]))
    assert pool["k"].shape == pool["v"].shape == (2, opts["num_blocks"], 128, 128)
    assert (pool["k"].size + pool["v"].size) * 2 == \
        opts["num_blocks"] * arch.kv_block_bytes(m, opts["block_size"])
    state = sum(a.size * a.dtype.itemsize for a in pool["state"].values())
    assert state == (opts["max_num_seqs"] + 1) * arch.state_bytes(m)
    assert arch.train_flops_per_token(m, 1) > 6 * arch.tree_params(m)


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    bench = harness.benchmark()
    assert len(bench["workloads"]) >= 9 and len(bench["configs"]) >= 7     # later PRs append
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell, entry = bench["workloads"][7], bench["configs"][5]
    assert cell["name"] == CELL and entry["name"] == "jamba2-3b"
    assert cell["chips"] == 1 and cell["traffic"] == "chat-burst" and len(cell["why"]) <= 200
    assert entry["reduced"] == [] and entry["file"] == CONFIG and len(entry["why"]) <= 200
    assert entry["source"] == \
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    assert set(e2e) == {"setup_s", "itl_p90_ms", "ttft_mean_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    assert set(NEW_METRICS) | {
        "decode_hbm_roofline", "decode_device_ms", "prefill_device_ms", "serve_idle_share",
        "kv_util_mean", "attn_keys_run_share", "setup_warm_s", "setup_deploy_s",
        "setup_weights_s", "compiles_in_window", "stream_send_ms", "stream_behind_share",
        "step_host_ms", "decode_bucket_fill_share", "prefill_token_fill_share"} <= set(layer)
    assert not [n for n in layer if n.startswith(("moe_", "ut_"))]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(NEW_METRICS)))        # appended, in the issue's order;
    assert names[first:first + 4] == list(NEW_METRICS)  # PR 41's two behind them
    assert names[first + 4:first + 6] == ["chunk_attn_kernel_share", "chunk_attn_time_share"]
    for name in NEW_METRICS:       # later cells read some of them too, behind it
        assert per_layer[name]["workloads"][0] == CELL
    assert per_layer["state_slot_util_share"]["layer"] == "engine scheduler and KV"
    assert per_layer["ssm_scan_time_share"]["source"] == "device_trace"
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    # every published key of the catalog's row, under its own name, unchanged
    config = harness.load_json(harness.ROOT, CONFIG)
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
        "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
        "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == [] and config["arch"] == "jamba"
    assert config["deployment"] == "the whole model on one chip"
    assert set(config["assumed"]) >= {"layer_order", "state_dtype", "head_dim", "weights"}
    opts = config["runners"]["requests"]["engine_options"]
    assert set(opts) == {"block_size", "max_num_seqs", "num_blocks", "prefill_chunk_tokens",
                         "max_step_tokens", "host_kv_bytes"}
    assert (opts["block_size"], opts["max_num_seqs"], opts["host_kv_bytes"]) == (128, 64, 0)
    # the pool: 64 sequences of 2,048 tokens and no more
    assert opts["num_blocks"] * opts["block_size"] == 64 * 2048
    whys = config["runners"]["requests"]
    assert all(name in whys for name in (
        "engine_options_why", "max_num_seqs_why", "block_size_why", "num_blocks_why",
        "prefill_chunk_why", "host_kv_bytes_why", "token_check_why", "token_tolerance_why"))
    check = config["runners"]["requests"]["token_check"]
    chunk = opts["prefill_chunk_tokens"]
    assert check["prompt_len"] > 2 * chunk and check["prompt_len"] % chunk  # a padded third chunk


def test_the_traffic_file_parses_and_its_schedule_is_the_same_for_two_seeds():
    mix = harness.load_json(harness.HERE, "traffic", "chat-burst.json")
    assert mix["kind"] == "requests" and mix["sharing"] is None and mix["max_total"] == 2048
    assert mix["arrivals"]["process"] == "gamma" and mix["arrivals"]["cv"] in (2.0, 1.5)
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"]) == (192, 0.8)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (16, 1536)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"]) == (96, 0.7)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (8, 384)
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert knee["rate_rps"] <= 0.85 * knee["knee_rps"]
    a = traffic.requests(mix, 4000000001, 45.0, 65536)
    b = traffic.requests(mix, 4000000002, 45.0, 65536)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_rps"] * 45)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(16 <= len(r.prompt) <= 1536 and 8 <= r.max_new_tokens <= 384
               and len(r.prompt) + r.max_new_tokens <= 2048 for r in a)
    assert 0 < a[0].due_s and a[-1].due_s < 45.0
    gaps = np.diff([r.due_s for r in a])
    assert gaps.std() / gaps.mean() > 1.2            # bursts: a Poisson process reads 1
    # warm-up reaches every lane bucket up to 64 and the widest table
    waves = traffic.warm_plan(mix, 45.0, 16, 64, 256)
    assert max(len(w) for w in waves[:-1]) >= 33


def test_program_refuses_a_checkout_without_the_model(monkeypatch):
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    monkeypatch.setattr(gpt, "CONFIGS", {k: v for k, v in gpt.CONFIGS.items()
                                         if k != "jamba2-3b"})
    with pytest.raises(SystemExit, match="no model 'jamba2-3b'"):
        arch.program(config, m)
    assert all(callable(getattr(arch, name)) for name in harness.ARCH_INTERFACE)


@pytest.fixture(scope="module")
def readings():
    """`scripts.jamba_tolerance` at the tiny preset: every reading is
    `bench_check_tokens` itself, on the engine's own greedy tokens."""
    import contextlib
    import io

    from scripts import jamba_tolerance

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert jamba_tolerance.main(
            ["--rehearse", "--seeds", "4000000003", "--parts", "wrong,faults,growth"]) == 0
    return json.loads(out.getvalue().splitlines()[-1])["rows"][0]


@pytest.mark.parametrize("control", [
    "state_zeroed_at_chunk_edges", "tail_zeroed_at_chunk_edges", "no_inner_norms",
    "padding_advances_the_state"])
def test_the_token_check_itself_fails_each_control(readings, control):
    """The benchmark's own check, not a copy of it: the sound engine inside,
    each wrong reference and the faulty program outside, fivefold and more.
    (The state held in bfloat16 is not separated at this size: PERF.md 7.)"""
    assert readings["ssm_tokens"][0] > 0 and readings["state_slots_claimed"] == 1
    assert readings["sound"]["token_err"] < 0.01
    assert readings[control]["token_err"] > 0.05


def test_a_perturbation_of_the_embedding_stays_small_on_its_way_to_the_logits(readings):
    """`--parts growth`: what a rounding grows by through the reference's
    layers, the number the preset's gains were settled by (under 10 at the
    published sizes; past some 50 a sound engine reads as float8 does)."""
    assert 1.0 < readings["growth"] < 10.0
