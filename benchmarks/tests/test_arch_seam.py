"""The seam holds for an architecture that is not GPT: the serving runner's
rehearsal, on the CPU, of fixture files alone (`fixtures/`: a configuration,
an architecture module with its own sizes, plain reference and costs), with
no file of the harness knowing them. And the harness's own files name no GPT
key or parameter."""

import copy
import os
import re
import time

import pytest

from benchmarks import harness, readers
from benchmarks.runners import serve as serve_runner

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
_GPT_NAMES = re.compile(r"n_embd|n_head|d_mlp|w_qkv|parallel_block|rotary_dim")
# where the GPT family may be spelled out: its module, its reference, its
# configurations, selfcheck's GPT cases, and an architecture fixture
_GPT_FILES = ("arch/", "reference.py", "configs/", "selfcheck.py", "tests/fixtures/")


@pytest.fixture(scope="module")
def runtime():
    os.makedirs(harness.OUT, exist_ok=True)
    rt = harness.Runtime(0)
    yield rt
    rt.stop()


def _rehearse(arch: str) -> dict:
    config = harness.load_json(FIXTURES, "llama-tiny.json")
    config["arch"] = arch
    harness.arch(arch)              # as `load_cell` does for a cell's file
    ctx = {"cell": {"name": "fixture.chat", "chips": 1}, "config": config,
           "traffic": harness.load_json(harness.HERE, "traffic", "chat-steady.json"),
           "bench": harness.benchmark(), "seed": 2 ** 31 + 5, "seconds": 3.0,
           "trace": True, "rehearse": True, "t0_wall": time.time(), "sweep": None}
    return serve_runner.run(ctx)


def test_fixture_architecture_passes_against_its_own_reference(runtime):
    obs = _rehearse("benchmarks.tests.fixtures.llama_arch")
    checks = obs["checks"]
    assert checks["tokens_match_reference"] and checks["token_err"] < 0.01, checks
    assert all(v for v in checks.values() if isinstance(v, bool)), checks
    assert obs["failed"] == 0 and obs["attempted"] > 0
    # what the readers ask of the module, and what the program recorded
    m = obs["facts"]["model"]
    assert obs["facts"]["arch"] == "benchmarks.tests.fixtures.llama_arch"
    assert obs["facts"]["kv_pool_bytes"] == 128 * 2 * 2 * 128 * 16 * 2
    assert harness.arch(obs["facts"]["arch"]).weight_bytes(m) == 4 * (
        2 * (4 * 128 * 128 + 3 * 128 * 352) + 128 * 512)
    assert readers.read("engine_step_ms", obs) > 0
    assert 0 <= readers.read("engine_wait_share", obs) <= 100
    assert readers.read("step_fetch_ms", obs) > 0
    assert obs["counters"]["total_tokens"] == obs["counters"]["engine_tokens"] > 0


@pytest.mark.parametrize("wrong", ["llama_gpt_reference", "llama_one_weight_off"])
def test_fixture_architecture_fails_against_a_wrong_reference(runtime, wrong):
    checks = _rehearse("benchmarks.tests.fixtures." + wrong)["checks"]
    assert not checks["tokens_match_reference"] and checks["token_err"] > 0.1, checks
    assert checks["every_response_exact"] and checks["counters_agree"], checks


def test_a_module_that_lacks_a_name_is_refused_before_any_chip():
    with pytest.raises(SystemExit, match="lacks"):
        harness.arch("benchmarks.peaks")
    with pytest.raises(ImportError):
        harness.arch("no_such_family")


def test_harness_files_name_no_gpt_key_or_parameter():
    found = []
    for root, _dirs, files in os.walk(harness.HERE):
        for name in files:
            path = os.path.join(root, name)
            rel = os.path.relpath(path, harness.HERE)
            if not name.endswith((".py", ".json")) or "__pycache__" in rel:
                continue
            if rel.startswith(_GPT_FILES) or rel == "tests/test_arch_seam.py":
                continue
            with open(path) as f:
                hits = sorted(set(_GPT_NAMES.findall(f.read())))
            if hits:
                found.append((rel, hits))
    assert not found, found


def test_every_configuration_resolves_through_its_module():
    # the module a configuration names turns its file into sizes that the
    # module's own costs and program model accept
    bench = harness.benchmark()
    for c in bench["configs"]:
        config = harness.load_json(harness.ROOT, c["file"])
        mod = harness.arch(config["arch"])
        for rehearse in (False, True):
            m = mod.dims(copy.deepcopy(config), rehearse)
            assert m["vocab_size"] > 0 and mod.weight_bytes(m) > 0
            assert mod.kv_block_bytes(m, 16) > 0 and mod.train_flops_per_token(m, 128) > 0
            name, overrides = mod.program(config, m)
            assert isinstance(name, str) and overrides["vocab_size"] == m["vocab_size"]
