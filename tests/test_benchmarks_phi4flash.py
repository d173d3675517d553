"""The `phi4-mini-flash.long-reason` cell: its rehearsal on the CPU through the
serving runner, as the driver's command runs it; every number of the
configuration's `reduced_why` against the tree `jax.eval_shape` gives at the
published sizes; the files `load_cell` finds; its traffic file; its three metric
files over what a run observed; the benchmark's own token check failing each
control at the tiny preset."""

import os
import time

import pytest

from benchmarks import harness, readers, selfcheck, traffic
from benchmarks.arch import phi4flash as arch
from benchmarks.runners import serve as serve_runner

CELL = "phi4-mini-flash.long-reason"
CONFIG = "benchmarks/configs/phi4-mini-flash.json"
NEW_METRICS = {
    "shared_kv_read_mb_step": {"kind": "counter_ratio", "num": "shared_kv_read_bytes",
                               "den": "steps_decode", "scale": 1e-6},
    "shared_kv_read_share": {"kind": "counter_ratio", "num": "shared_kv_read_bytes",
                             "den": "kv_read_bytes", "scale": 100.0},
    "cross_decoder_token_share": {"kind": "counter_ratio", "num": "cross_decoder_tokens",
                                  "den": "prefill_tokens", "scale": 100.0},
}


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 56, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_books_shared_rows_and_cross_decoder_tokens(obs):
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c = obs["counters"]
    assert c["state_slots_claimed"] >= c["total_finished"] > 0 and c["prefix_hits"] == 0
    assert c["window_blocks_released"] > 0
    assert c["cross_decoder_tokens"] == c["attn_chunks"] > 0        # one token a chunk program
    assert c["ssm_tokens_scanned"] == c["prefill_tokens_padded"] + c["decode_bucket_lanes"]
    m = obs["facts"]["model"]
    assert c["ssm_state_bytes"] == 2 * c["decode_lanes"] * arch.state_bytes(m)
    # the full layer and the one cross layer of the tiny preset against two window
    # layers: on the CPU every form covers the padded table, so exactly half
    assert readers.read("shared_kv_read_share", obs) == 50.0
    assert readers.read("shared_kv_read_mb_step", obs) == \
        1e-6 * c["shared_kv_read_bytes"] / c["steps_decode"] > 0
    share = readers.read("cross_decoder_token_share.itl", obs)
    assert share == 100.0 * c["cross_decoder_tokens"] / c["prefill_tokens"] and 0 < share < 20
    for name in ("ssm_state_mb_step", "attn_window_key_share", "state_slot_util_share.itl",
                 "ssm_masked_token_share.itl", "kv_util_mean_books.itl", "decode_lanes_mean_books",
                 "engine_step_ms_books"):
        assert readers.read(name, obs) > 0, name


def test_the_three_metric_files_read_a_canned_observation():
    for name, spec in NEW_METRICS.items():
        assert readers.reader_spec(name) == spec and spec["kind"] in readers.KINDS
    canned = {"counters": {"shared_kv_read_bytes": 4_000_000_000, "kv_read_bytes": 5_000_000_000,
                           "steps_decode": 100, "cross_decoder_tokens": 3, "prefill_tokens": 1200}}
    assert readers.read("shared_kv_read_mb_step", canned) == 40.0
    assert readers.read("shared_kv_read_share", canned) == 80.0
    assert readers.read("cross_decoder_token_share", canned) == 0.25
    # a program without the counters (the parent's) is read as nothing, not an error
    parent = {"counters": {"steps_decode": 10, "prefill_tokens": 50}, "trace": None}
    assert all(readers.read(name, parent) is None for name in NEW_METRICS)
    # a model no layer of which reads another's rows: the counters are there and read 0
    plain = {"counters": {"shared_kv_read_bytes": 0, "kv_read_bytes": 0, "steps_decode": 10,
                          "cross_decoder_tokens": 0, "prefill_tokens": 50}}
    assert readers.read("shared_kv_read_share", plain) is None
    assert readers.read("shared_kv_read_mb_step", plain) == 0.0


def test_weight_bytes_and_pool_bytes_equal_the_hand_count(obs):
    m = obs["facts"]["model"]
    # tiny preset: 8 layers, E 64, F 96, Di 128, N 16, R 8, K 4, 4 heads of 16 over 2
    layer = 4 * 64 + 64 * 192 + 96 * 64
    mamba = 64 * 256 + (128 * 4 + 128) + 128 * (8 + 32) + (8 * 128 + 128) + 128 * 16 + 128 + 128 * 64
    attn = (64 * 128 + 128) + (64 * 64 + 64) + 64 + 32
    cross = (64 * 64 + 64) + (64 * 64 + 64) + 64 + 32
    assert arch.layer_params(m) == layer and sum(arch.mamba_params(m).values()) == mamba
    assert sum(arch.attention_params(m, False).values()) == attn
    assert sum(arch.attention_params(m, True).values()) == cross
    assert arch.memory_params(m) == 2 * 64 * 128 and arch.pairs(m) == (3, 1)
    tree = 8 * layer + 3 * (mamba + attn) + 2 * 64 * 128 + cross + 500 * 64 + 128
    assert arch.tree_params(m) == tree and arch.weight_bytes(m) == 2 * tree
    assert arch.kv_block_bytes(m, 8) == 2 * 2 * 16 * 8 * 2
    assert obs["facts"]["kv_pool_bytes"] == 96 * arch.kv_block_bytes(m, 8)
    assert arch.state_bytes(m) == 3 * (128 * 16 * 4 + 128 * 3 * 2)
    assert arch.kernel_costs(m, 2, 8, 1)["ssm_scan"]["flops"] == 7.0 * 2 * 8 * 128 * 16


REDUCED_WHY = {     # every term of the configuration's `reduced_why`
    "layer": 78_653_440, "mamba": 41_241_600, "attention": 19_668_864, "memory": 26_214_400,
    "cross": 13_112_704, "embedding": 512_163_840, "tree": 3_852_562_944,
    "bytes": 7_705_125_888, "state": 3_225_600,
}


@pytest.fixture(scope="module")
def published():
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_params

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    return config, m, cfg, jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))


@pytest.mark.parametrize("term", list(REDUCED_WHY))
def test_each_number_of_reduced_why_is_the_trees_own(published, term):
    config, m, cfg, tree = published
    held = lambda prefix, layers: sum(a.size for n, a in tree.items() if n.startswith(prefix)) // layers
    common = arch.layer_params(m)
    got = {
        "layer": lambda: common,
        "mamba": lambda: held("sm_", 9) - common,
        "attention": lambda: held("sa_", 9) - common,
        "memory": lambda: held("cg_", 7) - common,
        "cross": lambda: held("ca_", 7) - common,
        "embedding": lambda: tree["tok_embed"].size,
        "tree": lambda: sum(a.size for a in tree.values()),
        "bytes": lambda: sum(a.size * a.dtype.itemsize for a in tree.values()),
        "state": lambda: arch.state_bytes(m),
    }[term]()
    assert got == REDUCED_WHY[term]
    assert f"{REDUCED_WHY[term]:,}" in config["reduced_why"]
    if term == "tree":
        assert cfg.n_params == arch.tree_params(m) == got
        assert 32 * 78_653_440 + 9 * 41_241_600 + 9 * 19_668_864 + 7 * 26_214_400 \
            + 7 * 13_112_704 + 512_163_840 + 5_120 == got
    if term == "mamba":
        assert sum(arch.mamba_params(m).values()) == got
        assert [arch.mamba_params(m)[k] for k in ("in_proj", "conv", "x_proj", "dt_proj", "A_log", "D", "out_proj")] \
            == [26_214_400, 25_600, 983_040, 824_320, 81_920, 5_120, 13_107_200]
    if term == "attention":
        assert list(arch.attention_params(m, False).values()) == [13_112_320, 6_556_160, 256, 128]
    if term == "cross":
        assert list(arch.attention_params(m, True).values()) == [6_556_160, 6_556_160, 256, 128]


def test_published_sizes_build_the_pool_and_the_state_the_file_states(published):
    import jax

    from ray_tpu.models.gpt import init_paged_cache, kv_layout

    config, m, cfg, tree = published
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == \
        (32, 2560, 40, 20, 64)
    assert (m["d_mlp"], m["window"], m["d_state"], m["d_conv"], m["expand"], m["dt_rank"]) == \
        (10240, 512, 16, 4, 2, 160)
    assert (m["vocab_size"], m["max_seq"], arch.pairs(m)) == (200064, 32768, (9, 7))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    opts = config["runners"]["requests"]["engine_options"]
    lay = kv_layout(cfg)
    assert lay.block_bytes(opts["block_size"], 2) == arch.kv_block_bytes(m, opts["block_size"]) \
        == 5120 * opts["block_size"]                # 5 KiB a token, ONE layer deep
    assert lay.state_bytes == arch.state_bytes(m)
    pool = jax.eval_shape(lambda: init_paged_cache(
        cfg, opts["num_blocks"], opts["block_size"], opts["max_num_seqs"]))
    assert pool["k"].shape == pool["v"].shape == (1, opts["num_blocks"], opts["block_size"], 1280)
    state = sum(a.size * a.dtype.itemsize for a in pool["state"].values())
    assert state == (opts["max_num_seqs"] + 1) * arch.state_bytes(m)
    assert arch.train_flops_per_token(m, 1) > 6 * arch.tree_params(m)


def test_the_cell_and_its_files_are_in_the_benchmark():
    loaded = harness.load_cell(CELL)        # configuration, traffic and architecture by name
    assert loaded["config"]["arch"] == "phi4flash" and loaded["traffic"]["kind"] == "requests"
    bench = harness.benchmark()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash")
    assert bench["workloads"].index(cell) == 10 and bench["configs"].index(entry) == 8  # appended
    assert cell["chips"] == 1 and cell["traffic"] == "long-reason-steady" and len(cell["why"]) <= 200
    assert entry["reduced"] == [] and entry["file"] == CONFIG and len(entry["why"]) <= 200
    assert entry["source"] == \
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    e2e = set(harness.cell_metrics(bench, CELL, "end_to_end"))
    assert {"setup_s", "itl_p90_ms"} <= e2e <= {"setup_s", "itl_p90_ms", "ttft_mean_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(NEW_METRICS)))           # appended, in PR 56's order; later PRs' behind
    assert [n.split(".")[0] for n in names[at:at + 3]] == list(NEW_METRICS)
    for name in names[at:at + 3]:
        assert per_layer[name]["workloads"] == [CELL] and name in layer
        assert per_layer[name]["source"] == "program_counter"
    assert {"decode_hbm_roofline", "decode_device_ms", "serve_idle_share", "setup_warm_s",
            "ssm_state_mb_step", "attn_window_key_share"} <= set(layer)
    assert not [n for n in layer if n.startswith(("moe_", "ut_", "ssd_"))]
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    # every published key of the catalog's row, under its own name, unchanged
    config = loaded["config"]
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
        "vocab_size": 200064}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == [] and config["deployment"]["what"] == "the whole model on one chip"
    assert list(config["assumed"])[:2] == ["differential_attention", "mamba_sizes"]
    assert set(config["assumed"]) >= {"no_inner_norms", "memory_handed_out", "layer_roles",
                                      "attention_bias", "state_dtype", "weights",
                                      "prefill_skips_the_cross_decoder", "served_positions"}
    opts = config["runners"]["requests"]["engine_options"]
    assert set(opts) == {"block_size", "max_num_seqs", "num_blocks", "prefill_chunk_tokens",
                         "max_step_tokens", "host_kv_bytes"}
    assert opts["host_kv_bytes"] == 0
    assert opts["max_step_tokens"] == opts["max_num_seqs"] + opts["prefill_chunk_tokens"]
    whys = config["runners"]["requests"]
    assert all(name in whys and len(whys[name]) > 40 for name in (
        "engine_options_why", "max_num_seqs_why", "block_size_why", "num_blocks_why",
        "prefill_chunk_why", "host_kv_bytes_why", "token_check_why", "token_tolerance_why"))
    check, chunk = whys["token_check"], opts["prefill_chunk_tokens"]
    assert check["prompt_len"] > 2 * chunk and check["prompt_len"] % chunk  # a padded third chunk
    assert check["prompt_len"] > config["sliding_window"] + opts["block_size"]  # a lane past its window


def test_selfcheck_passes_with_the_new_files():
    for check in (selfcheck.check_arithmetic, selfcheck.check_arch, selfcheck.check_files):
        check()


def test_the_traffic_file_parses_and_its_schedule_is_the_same_for_two_seeds():
    mix = harness.load_json(harness.HERE, "traffic", "long-reason-steady.json")
    assert mix["kind"] == "requests" and mix["sharing"] is None and mix["max_total"] == 20480
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                                 "min": 64, "max": 16384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.7,
                                 "min": 128, "max": 4096}
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert knee["rate_rps"] <= 0.81 * knee["knee_rps"] and len(knee["sweeps"]) >= 2
    a = traffic.requests(mix, 4000000001, 45.0, 200064)
    b = traffic.requests(mix, 4000000002, 45.0, 200064)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_rps"] * 45)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(64 <= len(r.prompt) <= 16384 and r.max_new_tokens <= 4096
               and len(r.prompt) + r.max_new_tokens <= 20480 for r in a)
    assert 0 < a[0].due_s and a[-1].due_s < 45.0
    config = harness.load_json(harness.ROOT, CONFIG)
    opts = config["runners"]["requests"]["engine_options"]
    waves = traffic.warm_plan(mix, 45.0, opts["block_size"], opts["max_num_seqs"],
                              opts["prefill_chunk_tokens"])
    lanes = opts["max_num_seqs"].bit_length()           # decode lane buckets 1 .. max
    assert (len(waves) - 1) * lanes + len(waves[-1]) <= 60      # the programs the cell warms


def test_program_refuses_a_checkout_without_the_model(monkeypatch):
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    monkeypatch.setattr(gpt, "CONFIGS", {k: v for k, v in gpt.CONFIGS.items()
                                         if k != "phi4-mini-flash"})
    with pytest.raises(SystemExit, match="no model 'phi4-mini-flash'"):
        arch.program(config, m)
    assert all(callable(getattr(arch, name)) for name in harness.ARCH_INTERFACE)


@pytest.fixture(scope="module")
def readings():
    """`scripts.phi4flash_tolerance` at the tiny preset: every reading is the
    harness's own `bench_check_tokens`."""
    import contextlib
    import io
    import json

    from scripts import phi4flash_tolerance

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert phi4flash_tolerance.main(
            ["--rehearse", "--seeds", "3000000001", "--parts", "wrong,faults"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])["rows"][0]


CONTROLS = ("no_lambda", "no_subln", "no_lambda_scale", "one_lambda_init",
            "window_one_block_wide", "m_after_gate", "m_without_skip", "gmu_own_input",
            "cross_reads_window_rows", "state_zeroed_at_chunk_edges",
            "tail_zeroed_at_chunk_edges", "no_ln_bias", "cross_decoder_on_the_last_slot")


@pytest.mark.parametrize("control", CONTROLS)
def test_the_benchmarks_token_check_fails_each_control_at_the_tiny_preset(readings, control):
    """The float32 engine of the rehearsal reads 0 against the reference; every
    control reads a hundredth or more of the largest logit."""
    assert readings["sound"]["token_err"] < 1e-4 and readings["sound"]["argmax_agree"] == 32
    assert readings[control]["token_err"] > 0.01, readings[control]
    assert readings["cross_decoder_tokens"] == [3, 70]
