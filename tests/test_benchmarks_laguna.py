"""The `laguna-xs2.codegen` cell's rehearsal on the CPU through the serving
runner, as the driver's command runs it (`benchmarks.runners.serve.run`); the
arithmetic of its architecture module and of the configuration file's
`reduced_why` against the initialised tree and the built pool at the published
sizes; its traffic file; its three metric files over what a run observed and
over a canned trace; a decode step's tiles on a hand-made routing and the new
counters' arithmetic on a hand-made table; and the benchmark's own token check failing each control at
the tiny preset."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import harness, readers, traffic
from benchmarks.arch import laguna as arch
from benchmarks.runners import serve as serve_runner

CELL = "laguna-xs2.codegen"
CONFIG = "benchmarks/configs/laguna-xs2.json"
NEW_METRICS = {
    "kv_window_block_share": {"kind": "counter_ratio", "num": "kv_window_block_ns",
                              "den": "kv_block_held_ns", "scale": 100.0},
    "attn_window_key_share": {"kind": "counter_ratio", "num": "attn_head_keys_window",
                              "den": "attn_head_keys", "scale": 100.0},
    "moe_grouped_time_share": {"kind": "trace_op_share",
                               "ops": ["moe_grouped_hidden", "moe_grouped_down"]},
}
CONTROLS = ["window_one_block_wide", "rotary_tables_swapped", "no_gate",
            "no_shared_expert", "float8_weights"]


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 43, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_keeps_the_new_books(obs):
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c, m = obs["counters"], obs["facts"]["model"]
    assert c["prefix_hits"] == 0 and c["window_blocks_released"] > 0
    assert 0 < c["kv_window_block_ns"] < c["kv_block_held_ns"]
    assert 0 < c["attn_head_keys_window"] < c["attn_head_keys"]
    # three of five layers are window layers and hold a window, not a context
    assert 0 < readers.read("kv_window_block_share", obs) < 60
    # 18 of 26 query heads are the window layers': tables of one tile here
    assert readers.read("attn_window_key_share", obs) == pytest.approx(100 * 18 / 26)
    assert readers.read("moe_grouped_time_share", obs) is None      # no device trace here
    for name in ("kv_util_mean", "queue_wait_p50_ms", "decode_lanes_mean", "engine_step_ms",
                 "attn_keys_run_share", "decode_chained_share", "prefill_token_fill_share",
                 "decode_bucket_fill_share", "stream_send_ms", "window_blocks_released",
                 "moe_experts_touched_mean", "moe_expert_load_max"):
        assert readers.read(name, obs) > 0, name


def test_the_three_metric_files_read_a_canned_observation():
    for name, spec in NEW_METRICS.items():
        assert readers.reader_spec(name) == spec and spec["kind"] in readers.KINDS
    canned = {
        "counters": {"kv_window_block_ns": 2_000, "kv_block_held_ns": 10_000,
                     "attn_head_keys_window": 30, "attn_head_keys": 120},
        "trace": {"busy_s": 2.0, "window_s": 5.0,
                  "op_self_s": {"moe_grouped_hidden": 0.5, "moe_grouped_down.1": 0.3,
                                "fusion": 1.2}},
    }
    assert readers.read("kv_window_block_share", canned) == 20.0
    assert readers.read("attn_window_key_share", canned) == 25.0
    assert abs(readers.read("moe_grouped_time_share", canned) - 40.0) < 1e-9
    # a program without the counters (the parent's) is read as nothing, not an error
    parent = {"counters": {"steps_decode": 10}, "trace": None}
    assert all(readers.read(name, parent) is None for name in NEW_METRICS)
    # a model without window layers: the counters are there and read 0
    plain = {"counters": {"kv_window_block_ns": 0, "kv_block_held_ns": 0}}
    assert readers.read("kv_window_block_share", plain) is None


def test_a_decode_step_runs_one_tile_of_128_rows_an_expert_touched():
    """Three lanes over eight experts, top-2: experts {0, 1}, {1, 2}, {1, 5}
    are four experts touched, each a tile of its own in what `dropless_groups`
    lays out: the formula PERF.md 5 reads a decode step's tile fill by
    (top_k x lanes / (128 x `moe_experts_touched_mean`)); no counter repeats it."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    combine = np.zeros((3, 8), np.float32)
    for lane, experts in enumerate([(0, 1), (1, 2), (1, 5)]):
        combine[lane, list(experts)] = 0.5
    _rows, tile_expert, _first, tiles = moe.dropless_groups(
        jnp.asarray(combine), 2, moe.GROUP_ROWS)
    touched, _share = moe.dropless_load(jnp.asarray(combine))
    assert int(tiles) == int(touched) == 4 and tile_expert[:4].tolist() == [0, 1, 2, 5]
    assigned, tiled = int((combine > 0).sum()), int(tiles) * moe.GROUP_ROWS
    assert (assigned, tiled) == (6, 512) and 100 * assigned / tiled == pytest.approx(1.171875)


def test_window_block_nanoseconds_on_a_hand_made_table():
    """One sequence of 40 tokens in blocks of 8 over a global and a window
    group (window 16): 5 + 3 blocks at rest; the engine's tick books each
    count times the nanoseconds it stood."""
    from ray_tpu.serve.engine import KVBlockManager

    mgr = KVBlockManager(32, 8, group_windows=(0, 16))
    prompt = list(range(1, 41))
    mgr.allocate_cached("s", prompt, 41)
    for start in range(0, 40, 8):
        mgr.slide("s", start, start + 8)
        mgr.register_computed("s", prompt, start + 8)
    mgr.grow("s", 41, first_query=40)       # the first decode step's block
    mgr.check_invariants()
    assert (mgr.blocks_held, mgr.window_blocks_held) == (6 + 3, 3)
    assert mgr.held_blocks("s") == [6, 3] and mgr.window_released == 3
    books = {"kv_block_held_ns": 0, "kv_window_block_ns": 0}
    for dt in (1_000, 250):        # two readings, as `InferenceEngine._tick_slots` makes them
        books["kv_block_held_ns"] += dt * mgr.blocks_held
        books["kv_window_block_ns"] += dt * mgr.window_blocks_held
    assert books == {"kv_block_held_ns": 11_250, "kv_window_block_ns": 3_750}
    mgr.free("s")
    assert (mgr.blocks_held, mgr.window_blocks_held) == (0, 0)


def test_published_sizes_give_the_issues_bytes_and_a_built_tree_and_pool():
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, init_params, kv_layout

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    assert (m["n_layers"], m["dense_layers"], m["d_model"], m["d_head"]) == (5, 1, 2048, 128)
    assert (m["n_heads"], m["n_heads_window"], m["n_kv_heads"]) == (48, 64, 8)
    assert m["window_layout"] == [0, 1, 1, 1, 0] and m["window"] == 512
    assert (m["n_experts"], m["top_k"], m["d_expert"], m["d_shared"], m["d_dense"]) == \
        (256, 8, 512, 512, 8192)
    assert (m["vocab_size"], m["max_seq"], m["route_scale"]) == (100352, 32768, 2.5)
    # the issue's arithmetic, redone in the file's `reduced_why`
    assert arch.attention_params(m, 0) == 29_458_432 and arch.attention_params(m, 1) == 37_879_808
    assert arch.layer_params(m, 0, 256) == 79_794_176
    assert arch.layer_params(m, 1, 256) == 846_860_288
    assert arch.layer_params(m, 4, 256) == 838_438_912
    assert arch.tree_params(m) == 3_869_857_792
    for number in ("29,458,432", "37,879,808", "50,331,648", "808,976,384", "411,041,792",
                   "79,794,176", "846,860,288", "838,438,912", "3,869,857,792"):
        assert number in config["reduced_why"], number
    # a stated LOWER bound: what a step of one lane must read
    assert arch.weight_bytes(m) == 2 * (
        sum(arch.layer_params(m, l, 8) for l in range(5)) + 2048 * 100352 + 2048)
    assert 1.08e9 < arch.weight_bytes(m) < 1.10e9 < 2 * arch.tree_params(m)
    opts = config["runners"]["requests"]["engine_options"]
    assert arch.kv_block_bytes(m, opts["block_size"]) == 262_144      # one layer, 4 KiB a token
    # the program's own tree and pool at these sizes: EXACTLY those bytes
    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    assert cfg == CONFIGS[name](n_layers=5, max_seq=32768)      # every published value is the preset's
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    assert sum(a.size for a in tree.values()) == arch.tree_params(m) == cfg.n_params
    lay = kv_layout(cfg)
    assert len(lay.windows) == 5 and lay.depth == 1
    assert lay.block_bytes(opts["block_size"], 2) == arch.kv_block_bytes(m, opts["block_size"])
    pool = jax.eval_shape(lambda: init_paged_cache(cfg, opts["num_blocks"], opts["block_size"]))
    assert pool["k"].shape == pool["v"].shape == (1, opts["num_blocks"], 64, 1024)
    assert (pool["k"].size + pool["v"].size) * 2 == \
        opts["num_blocks"] * arch.kv_block_bytes(m, opts["block_size"]) == 4 << 30
    assert arch.train_flops_per_token(m, 1) > 6 * arch.weight_bytes(m) / 2
    assert arch.kernel_costs(m, 1, 1, 1) == {}


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    bench = harness.benchmark()
    assert len(bench["workloads"]) >= 9 and len(bench["configs"]) >= 7   # later PRs append
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell, entry = bench["workloads"][8], bench["configs"][6]
    assert cell["name"] == CELL and entry["name"] == "laguna-xs2"
    assert cell["chips"] == 1 and cell["traffic"] == "codegen-steady" and len(cell["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers"] and entry["file"] == CONFIG
    assert len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    assert set(e2e) == {"setup_s", "itl_p90_ms", "ttft_mean_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    assert set(NEW_METRICS) | {
        "decode_hbm_roofline", "decode_device_ms", "prefill_device_ms", "serve_idle_share",
        "kv_util_mean", "attn_keys_run_share", "window_blocks_released",
        "moe_experts_touched_mean", "moe_expert_load_max", "chunk_attn_kernel_share",
        "chunk_attn_time_share", "setup_warm_s", "compiles_in_window", "stream_send_ms",
        "step_host_ms", "decode_bucket_fill_share", "prefill_token_fill_share"} <= set(layer)
    assert not {"moe_held_assign_share", "moe_empty_layer_share", "moe_grouped_token_share"} \
        & set(layer)
    assert not [n for n in layer if n.startswith(("ssm_", "ut_", "state_"))]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(NEW_METRICS)))        # appended, in the issue's order;
    last = first + len(NEW_METRICS)                     # PR 44's two behind them, PR 49's one,
    assert names[first:last] == list(NEW_METRICS)       # PR 50's one (the same reader, this cell too)
    assert names[last:last + 4] == ["decode_attn_kernel_share", "decode_attn_time_share",
                                    "relayout_time_share", "relayout_time_share.itl"]    # PR 51's behind
    assert {"decode_attn_kernel_share", "decode_attn_time_share",
            "relayout_time_share.itl"} <= set(layer)
    for name in NEW_METRICS:       # later cells read `moe_grouped_time_share` too, behind it
        assert per_layer[name]["workloads"][0] == CELL
    assert per_layer["kv_window_block_share"]["layer"] == "engine scheduler and KV"
    assert per_layer["moe_grouped_time_share"]["source"] == "device_trace"
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    # every published key of the catalog's row, under its own name, unchanged
    config = harness.load_json(harness.ROOT, CONFIG)
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
        "moe_routed_scaling_factor": 2.5}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 5 and config["published"] == {"num_hidden_layers": 40}
    assert config["layer_types"] == (["full_attention"] + ["sliding_attention"] * 3) * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    rope = config["rope_parameters"]
    assert rope["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
        "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5}
    assert rope["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    assert rope["original_max_position_embeddings"] == 4096
    assert config["reduced"] == ["num_hidden_layers"] and config["arch"] == "laguna"
    dep = config["deployment"]
    assert (dep["stages"], dep["layers_a_stage"], dep["stage"], dep["served_positions"]) == \
        (8, 5, 0, 32768)
    assert set(config["assumed"]) >= {
        "rotary", "gating", "no_qk_norm", "activation", "shared_expert", "routing", "weights"}
    opts = config["runners"]["requests"]["engine_options"]
    assert set(opts) == {"block_size", "max_num_seqs", "num_blocks", "prefill_chunk_tokens",
                         "max_step_tokens", "host_kv_bytes"}
    assert (opts["block_size"], opts["num_blocks"], opts["host_kv_bytes"]) == (64, 16384, 0)
    assert opts["max_step_tokens"] == opts["max_num_seqs"] + opts["prefill_chunk_tokens"]
    whys = config["runners"]["requests"]
    assert all(name in whys for name in (
        "engine_options_why", "max_num_seqs_why", "block_size_why", "num_blocks_why",
        "prefill_chunk_why", "host_kv_bytes_why", "token_check_why", "token_tolerance_why"))
    check = whys["token_check"]
    # nine windows deep and more, past YaRN's original positions, a table past one tile
    assert check["prompt_len"] > 9 * config["sliding_window"] > 4096
    assert check["prompt_len"] % opts["prefill_chunk_tokens"] and check["new_tokens"] == 1024
    sizes = config["rehearsal"]["sizes"]
    assert sizes["num_attention_heads_per_layer"] == [4, 6, 6, 6, 4]
    assert sizes["sliding_window"] < config["rehearsal"]["requests"]["prompt_len"]["min"]
    assert sizes["num_experts"] * 128 > 32 * sizes["num_experts_per_tok"]   # tiles a chunk cannot fill


def test_the_traffic_file_parses_and_its_schedule_is_the_same_for_two_seeds():
    mix = harness.load_json(harness.HERE, "traffic", "codegen-steady.json")
    assert mix["kind"] == "requests" and mix["sharing"] is None and mix["max_total"] == 32768
    assert mix["arrivals"]["process"] == "poisson"
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"]) == (3072, 0.9)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (256, 24576)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"]) == (384, 0.6)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (64, 1536)
    assert mix["trace"] == {"after_s": 20.0, "seconds": 5.0}
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert knee["rate_rps"] <= 0.81 * knee["knee_rps"]
    assert len(knee["sweeps"]) >= 2
    a = traffic.requests(mix, 5000000001, 45.0, 100352)
    b = traffic.requests(mix, 5000000002, 45.0, 100352)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_rps"] * 45)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(256 <= len(r.prompt) <= 24576 and 64 <= r.max_new_tokens <= 1536
               and len(r.prompt) + r.max_new_tokens <= 32768 for r in a)
    assert 0 < a[0].due_s and a[-1].due_s < 45.0
    # nearly every prompt is past the window
    assert sum(len(r.prompt) > 512 for r in a) >= 0.9 * len(a)


def test_program_refuses_a_checkout_without_the_model(monkeypatch):
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    monkeypatch.setattr(gpt, "CONFIGS", {k: v for k, v in gpt.CONFIGS.items()
                                         if k != "laguna-xs2"})
    with pytest.raises(SystemExit, match="no model 'laguna-xs2'"):
        arch.program(config, m)
    assert all(callable(getattr(arch, name)) for name in harness.ARCH_INTERFACE)


@pytest.fixture(scope="module")
def readings():
    """`scripts.laguna_tolerance` at the tiny preset: every reading is
    `bench_check_tokens` itself, on the engine's own greedy tokens."""
    import contextlib
    import io

    from scripts import laguna_tolerance

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert laguna_tolerance.main(
            ["--rehearse", "--seeds", "5000000003", "--parts", "wrong,float8,growth"]) == 0
    return json.loads(out.getvalue().splitlines()[-1])["rows"][0]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_token_check_itself_fails_each_control(readings, control):
    """The benchmark's own check, not a copy of it: the sound engine inside,
    each wrong reference and the float8 weights outside, two and a half times
    its bound and more."""
    assert readings["window_blocks_released"] > 0
    assert readings["sound"]["token_err"] < 0.01
    assert readings[control]["token_err"] > 0.025


def test_top_seven_for_top_eight_is_a_swap_the_check_does_not_tell_apart(readings):
    """Named, not hidden (PERF.md 7 (2)): the kept sigmoid scores are all near
    1, so dropping the last of them moves what ONE swap of a near-tie moves,
    and the routed experts' output gain is small so that such swaps, which
    bfloat16 makes in a sound engine, stay under the limit."""
    assert readings["top_k_minus_one"]["token_err"] < readings["float8_weights"]["token_err"]


def test_a_perturbation_of_the_embedding_stays_small_on_its_way_to_the_logits(readings):
    """`--parts growth`: what a rounding grows by through the reference's
    layers (34-35fold at the published widths before the routed experts'
    output gain was cut to 0.1: PERF.md 6, PR 43)."""
    assert 1.0 < readings["growth"] < 10.0
