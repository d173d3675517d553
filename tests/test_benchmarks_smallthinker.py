"""The new cell's rehearsal on the CPU through the serving runner, as the
driver's command runs it (`benchmarks.runners.serve.run`), and the
arithmetic of its architecture module against a hand count."""

import json
import os
import time

import pytest

from benchmarks import harness, readers
from benchmarks.arch import smallthinker as arch
from benchmarks.runners import serve as serve_runner

CELL = "smallthinker-21b-a3b.mixed-len"


@pytest.fixture(scope="module")
def obs():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    try:
        loaded = harness.load_cell(CELL)
        yield serve_runner.run(dict(
            loaded, seed=2 ** 31 + 11, seconds=4.0, trace=True, rehearse=True,
            t0_wall=time.time(), sweep=None))
    finally:
        rt.stop()


def test_rehearsal_is_correct_and_counts_what_the_layer_did(obs):
    checks = obs["checks"]
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.01
    assert obs["failed"] == 0 and obs["attempted"] > 0
    c = obs["counters"]
    assert c["window_blocks_released"] > 0
    steps = [ev["args"] for ev in obs["spans"]
             if ev["name"] == "engine.step" and ev["args"].get("decodes")]
    assert steps and all(2 <= a["experts_touched"] <= 8 for a in steps)
    assert all(0 < a["expert_load_max"] <= 0.5 for a in steps)
    for name in ("moe_experts_touched_mean", "moe_expert_load_max",
                 "window_blocks_released", "kv_util_mean", "prefill_span_p90_ms",
                 "decode_lanes_mean", "engine_step_ms"):
        assert readers.read(name, obs) > 0, name


def test_rehearsal_keeps_the_gauges_integer_books(obs):
    """ISSUE 53: what the expert model's step record carries as floats has
    integer books among the rehearsal's counts, and each new metric reads
    what its span twin reads over the same window (the two windows differ
    by the steps at their edges)."""
    c = obs["counters"]
    new = ("kv_block_cap_ns", "moe_steps_read", "moe_experts_touched_milli",
           "moe_load_max_ppm", "between_ns", "flight_spans_recorded")
    assert all(type(c[k]) is int and c[k] > 0 for k in new), {k: c.get(k) for k in new}
    assert c["ut_steps_read"] == c["ut_exit_step_milli"] == c["flight_spans_dropped"] == 0
    assert abs(c["moe_steps_read"] - c["steps_decode"]) <= 2    # a step in flight at an edge
    # exact where the engine is stepped by hand (`test_serve_engine.py`); here
    # the window's two readings come from another thread than the one that books
    assert c["between_ns"] + sum(c[k] for k in (
        "sched_ns", "side_ns", "build_ns", "dispatch_ns", "fetch_ns",
        "sample_ns")) == pytest.approx(c["step_ns"], rel=1e-3)
    # the pool over TIME: the books' window runs on past the spans' 4 s, to the
    # runner's `bench_window_end` call, and nothing arrives in that part
    for books, twin, rel in (
            ("moe_experts_touched_mean_books", "moe_experts_touched_mean", 0.1),
            ("moe_expert_load_max_books", "moe_expert_load_max", 0.1),
            ("decode_lanes_mean_books", "decode_lanes_mean", 0.1),
            ("kv_util_mean_books", "kv_util_mean", 0.3)):
        assert readers.read(books, obs) == pytest.approx(readers.read(twin, obs), rel=rel), books
    assert readers.read("flight_drop_share", obs) == 0.0
    assert 0.0 < readers.read("step_between_ms", obs) < readers.read("engine_step_ms_books", obs)
    steps = [ev for ev in obs["spans"] if ev["name"] == "engine.step"]
    assert len(steps) >= 0.9 * c["steps"]       # the window's records all came back


def test_weight_bytes_and_pool_bytes_equal_the_hand_count(obs):
    m = obs["facts"]["model"]
    # a layer: q 64x64, k and v 64x32 each, o 64x64; router 64x8; 2 of the 8
    # experts of 3 x 64 x 32; the head 64x500; 2 bytes: a LOWER bound
    assert arch.weight_bytes(m) == 2 * (4 * (12288 + 512 + 2 * 6144) + 32000)
    # 1 global + 3 window layers: groups of one layer; K and V, 2 heads of 16,
    # 8 tokens, bf16
    assert arch.kv_block_bytes(m, 8) == 2 * 1 * 2 * 16 * 8 * 2
    assert obs["facts"]["kv_pool_bytes"] == 256 * 1024
    assert arch.kernel_costs(m, 1, 1, 1) == {}


def test_published_sizes_give_the_issues_bytes():
    config = harness.load_json(harness.ROOT, "benchmarks/configs/smallthinker-21b-a3b.json")
    m = arch.dims(config, False)
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]) == (2560, 28, 4, 128)
    assert (m["n_experts"], m["d_expert"], m["top_k"]) == (64, 768, 6)
    assert (m["window"], m["rope_theta"], m["vocab_size"], m["max_seq"]) == (
        4096, 1500000.0, 151936, 16384)
    assert m["rope_layout"] == m["window_layout"] == [0, 1, 1, 1] * 3
    assert config["reduced"] == ["num_hidden_layers"] and m["n_layers"] == 12
    assert arch.layer_params(m, 64) == 398_622_720          # 398.6 M a layer
    # lower bound of a decode step: 6 experts a layer and the head
    assert arch.weight_bytes(m) == 2 * (12 * (20_971_520 + 163_840 + 6 * 5_898_240)
                                        + 2560 * 151936)
    opts = config["runners"]["requests"]["engine_options"]
    assert arch.kv_group_layers(m) == 3
    assert arch.kv_block_bytes(m, opts["block_size"]) == 2 * 3 * 512 * 64 * 2
    # the program's own tree at these sizes: 11.12 GB in bfloat16
    import jax

    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, init_params

    name, overrides = arch.program(config, m)
    cfg = CONFIGS[name](**overrides)
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert all(a.dtype == jax.numpy.bfloat16 for a in tree.values())
    assert sum(a.size for a in tree.values()) * 2 == 11_123_087_360
    pool = jax.eval_shape(lambda: init_paged_cache(cfg, opts["num_blocks"], opts["block_size"]))
    assert 2 * pool["k"].size * 2 == opts["num_blocks"] * arch.kv_block_bytes(m, opts["block_size"])


def test_the_cell_and_its_files_are_in_the_benchmark():
    from benchmarks.tests.test_arch_seam import (
        test_every_configuration_resolves_through_its_module as resolves)

    resolves()
    bench = harness.benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mixed-len-steady"
    assert len(cell["why"]) <= 200
    e2e = harness.cell_metrics(bench, CELL, "end_to_end")
    assert "setup_s" in e2e and set(e2e) & {"ttft_mean_ms", "itl_p90_ms"}
    for name in harness.cell_metrics(bench, CELL, "per_layer"):
        assert readers.reader_spec(name)["kind"] in readers.KINDS, name
    mix = harness.load_json(harness.HERE, "traffic", "mixed-len-steady.json")
    assert mix["sharing"] is None and mix["max_total"] == 16384
    assert json.dumps(bench).count(CELL) >= 25


@pytest.fixture(scope="module")
def readings():
    """`scripts.smallthinker_tolerance` at the tiny preset: every reading is
    `bench_check_tokens` itself, on the engine's own greedy tokens."""
    import contextlib
    import io

    from scripts import smallthinker_tolerance

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert smallthinker_tolerance.main(["--rehearse", "--seeds", "5"]) == 0
    return json.loads(out.getvalue().splitlines()[-1])["rows"][0]


@pytest.mark.parametrize("control", [
    "top_k_minus_one", "window_off", "window_one_block_wide", "rope_on_nope_layers",
    "float8_weights"])
def test_the_token_check_itself_fails_each_control(readings, control):
    """The benchmark's own check, not a copy of it: the sound engine inside,
    each wrong reference and the float8-weights engine outside, threefold."""
    assert readings["distinct_tokens"] > 16
    assert readings["sound"]["token_err"] < 0.01
    assert readings[control]["token_err"] > 0.03
