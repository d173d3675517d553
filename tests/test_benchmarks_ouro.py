"""The `ouro-2.6b.reason` cell's rehearsal on the CPU through the serving
runner, as the driver's command runs it (`benchmarks.runners.serve.run`), and
the arithmetic of its architecture module against a hand count."""

import json
import os
import time

import pytest

from benchmarks import harness, readers
from benchmarks.arch import ouro as arch
from benchmarks.runners import serve as serve_runner

CELL = "ouro-2.6b.reason"
CONFIG = "benchmarks/configs/ouro-2.6b.json"


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 13, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_reads_both_new_metrics(obs):
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.03
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c = obs["counters"]
    steps = [ev["args"] for ev in obs["spans"] if ev["name"] == "engine.step"]
    decodes = [a for a in steps if a["decodes"]]
    assert decodes and all(
        a["ut_passes"] == 4 and 1.0 < a["exit_step_mean"] < 4.0
        and 0.0 < a["exit_cdf_early"] < 1.0 for a in decodes)
    # four passes a decoded token, by the length of what the program hands back
    assert c["ut_passes_run"] == c["ut_passes_full"] > 0 == c["ut_passes_run"] % 4
    assert readers.read("ut_passes_run_share", obs) == 100.0
    assert 1.0 < readers.read("ut_exit_step_mean", obs) < 4.0
    for name in ("kv_util_mean.itl", "prefill_span_p90_ms.itl", "queue_wait_p50_ms.itl",
                 "decode_lanes_mean", "engine_step_ms"):
        assert readers.read(name, obs) > 0, name


def test_rehearsal_keeps_the_exit_gates_integer_books(obs):
    """ISSUE 53: the looped model's `exit_step_mean` rides the step record
    as a float; its integer books are among the rehearsal's counts and
    their ratio reads what the span twin reads."""
    c = obs["counters"]
    new = ("kv_block_cap_ns", "ut_steps_read", "ut_exit_step_milli",
           "between_ns", "flight_spans_recorded")
    assert all(type(c[k]) is int and c[k] > 0 for k in new), {k: c.get(k) for k in new}
    assert c["moe_steps_read"] == c["moe_experts_touched_milli"] == c["moe_load_max_ppm"] == 0
    assert abs(c["ut_steps_read"] - c["steps_decode"]) <= 2     # a step in flight at an edge
    assert c["flight_spans_dropped"] == 0
    # the pool over TIME: the books' window runs on past the spans' 4 s, to the
    # runner's `bench_window_end` call
    for books, twin, rel in (("ut_exit_step_mean_books", "ut_exit_step_mean", 0.1),
                             ("decode_lanes_mean_books", "decode_lanes_mean", 0.1),
                             ("kv_util_mean_books.itl", "kv_util_mean.itl", 0.3)):
        assert readers.read(books, obs) == pytest.approx(readers.read(twin, obs), rel=rel), books
    assert readers.read("flight_drop_share", obs) == 0.0
    assert 0.0 < readers.read("step_between_ms", obs) < readers.read("engine_step_ms_books", obs)


def test_weight_bytes_and_pool_bytes_equal_the_hand_count(obs):
    m = obs["facts"]["model"]
    # a layer: q, k, v, o of 64x64 and gate, up, down of 64x96; 3 layers read
    # 4 times; the head 64x500; 2 bytes
    assert arch.weight_bytes(m) == 2 * (4 * 3 * (4 * 4096 + 3 * 6144) + 32000)
    # K and V, 4 x 3 (pass, layer) pairs, 4 heads of 16, 8 tokens, bf16
    assert arch.kv_block_bytes(m, 8) == 2 * 12 * 64 * 8 * 2
    assert obs["facts"]["kv_pool_bytes"] == 48 * 24576
    assert arch.kernel_costs(m, 1, 1, 1) == {}


def test_published_sizes_give_the_issues_bytes():
    config = harness.load_json(harness.ROOT, CONFIG)
    m = arch.dims(config, False)
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == (2048, 16, 16, 128)
    assert (m["n_layers"], m["d_mlp"], m["ut_steps"], m["exit_threshold"]) == (48, 5632, 4, 1.0)
    assert (m["rope_theta"], m["vocab_size"], m["max_seq"]) == (1e6, 49152, 65536)
    assert config["reduced"] == [] and set(config["assumed"]) >= {
        "sandwich_norms", "norm_between_passes", "cache_per_pass", "rotary_layout",
        "exit_gate", "weights"}
    assert arch.layer_params(m) == 51_380_224                # 51.38 M a layer
    # a decode step streams the 48 layers four times and the head: 19.9 GB
    assert arch.weight_bytes(m) == 2 * (4 * 48 * 51_380_224 + 2048 * 49152) == 19_931_332_608
    # 4 passes x 48 layers x (K, V) x 2048 x 2 bytes a token
    assert arch.kv_block_bytes(m, 1) == 1_572_864
    opts = config["runners"]["requests"]["engine_options"]
    assert arch.kv_block_bytes(m, opts["block_size"]) == 24 << 20
    assert arch.train_flops_per_token(m, 1) > 6 * 4 * 48 * 51_380_224
    # the program's own tree at these sizes: 5.34 GB in bfloat16, a pool 192 deep
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, init_params

    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    assert cfg == CONFIGS[name]()           # the preset IS the published keys
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    assert 5.33e9 < sum(a.size for a in tree.values()) * 2 < 5.35e9
    pool = jax.eval_shape(lambda: init_paged_cache(cfg, opts["num_blocks"], opts["block_size"]))
    assert pool["k"].shape[0] == 192
    assert 2 * pool["k"].size * 2 == opts["num_blocks"] * arch.kv_block_bytes(m, opts["block_size"])


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    bench = harness.benchmark()
    assert len(bench["workloads"]) >= 6 and len(bench["configs"]) >= 4
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-steady"
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [] and entry["file"] == CONFIG and len(entry["why"]) <= 200
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    # `ttft_mean_ms` spread over half its bound at every rate tried (the
    # traffic file's `ttft_spread`): the cell reports the gap and the set-up,
    # of `gpt2-large.chat`'s per-layer metrics those that move them, and the
    # pool, admission, prefill and first-token readings as `<reader>.itl`
    assert set(e2e) == {"setup_s", "itl_p90_ms"}
    layer = harness.cell_metrics(bench, CELL, "per_layer")
    assert {"ut_passes_run_share", "ut_exit_step_mean", "decode_hbm_roofline",
            "decode_device_ms", "preemptions", "setup_weights_s",
            "kv_util_mean.itl", "queue_wait_p50_ms.itl", "prefill_span_p90_ms.itl",
            "prefill_device_ms.itl", "ttft_p50_ms.itl", "ttft_p90_ms.itl",
            "ttft_mean_ms.itl"} <= set(layer)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(per_layer[name]["moves"] in e2e for name in layer)
    chat = harness.cell_metrics(bench, "gpt2-large.chat", "per_layer")
    assert {n for n in chat if per_layer[n]["moves"] in e2e} <= set(layer)
    for name in layer:
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    mix = harness.load_json(harness.HERE, "traffic", "reason-steady.json")
    assert mix["sharing"] is None and mix["max_total"] == 2048
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"]) == (192, 0.8)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (32, 1024)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"]) == (256, 0.7)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (64, 1024)
    knee = mix["knee_sweep"]
    assert abs(mix["arrivals"]["rate_rps"] - knee["rate_rps"]) < 1e-9
    assert knee["rate_rps"] <= 0.85 * knee["knee_rps"]
    # every published key of the catalog's row, under its own name
    published = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 5632, "max_position_embeddings": 65536,
                 "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
                 "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152}
    config = harness.load_json(harness.ROOT, CONFIG)
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48


@pytest.fixture(scope="module")
def readings():
    """`scripts.ouro_tolerance` at the tiny preset: every reading is
    `bench_check_tokens` itself, on the engine's own greedy tokens."""
    import contextlib
    import io

    from scripts import ouro_tolerance

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert ouro_tolerance.main(["--rehearse", "--seeds", "5"]) == 0
    return json.loads(out.getvalue().splitlines()[-1])["rows"][0]


@pytest.mark.parametrize("control", [
    "three_passes_for_four", "every_pass_on_the_first_pass_cache",
    "no_norm_between_passes", "no_post_norms", "second_block_unseen",
    "float8_weights"])
def test_the_token_check_itself_fails_each_control(readings, control):
    """The benchmark's own check, not a copy of it: the sound engine inside,
    each wrong reference and the float8-weights engine outside, threefold."""
    assert readings["distinct_tokens"] > 16
    assert readings["ut_passes"][0] == readings["ut_passes"][1] > 0
    assert readings["sound"]["token_err"] < 0.01
    assert readings[control]["token_err"] > 0.03
