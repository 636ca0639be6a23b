"""A new model family goes in as new files: a configuration whose
``"model"`` object sets the program's expert settings, a family module
with its reference, weight rules and counts, and a cell.  In a copy of
``bench/`` with only those files added, a sound run of the program's
sparse-expert decoder is correct, a token altered where sampled is not,
and every file the copy shares with the repository is unchanged.  A
family module that lacks a function, and a model setting the program
does not have, are refused by name."""
import json
import shutil
import time

import pytest

from benchroot import REPO, TINY_CONFIG, make_tiny_root
from harness.main import MODEL_KEYS, model_config, run_cell
from harness.spec import FAMILY_API, Bench, SpecError

DATA = REPO / "tests" / "bench" / "data"
TOY_MOE = dict(TINY_CONFIG, arch="olmoe-1b-7b", reference="toy_moe", d_ff=32,
               mlp="swiglu_moe",
               model={"n_experts": 4, "top_k": 2, "moe_capacity_factor": 2.0,
                      "use_qk_norm": True})
LIMIT = 0.003   # toy-size readings, seeds 1-8: program <= 0.00029, fp8 control >= 0.0074


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("moe_root"))
    bench = root / "bench"
    shutil.copy(DATA / "toy_moe.py", bench / "references" / "toy_moe.py")
    (bench / "configs" / "toy_moe.json").write_text(json.dumps(TOY_MOE))
    (bench / "cells" / "toy_moe.chat.json").write_text(json.dumps(
        {"rate_per_s": 8.0, "logit_gap_limit": LIMIT, "min_compared_tokens": 20}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "toy_moe.chat", "config": "toy_moe",
                              "traffic": "tiny_chat", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.chat" in m.get("workloads", []):
            m["workloads"].append("toy_moe.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root, seed, traced=False, **kw):
    return run_cell(root, "toy_moe.chat", seed, 1.0, traced, t_start=time.perf_counter(),
                    require_tpu=False, log=lambda *a, **k: None, **kw)


def test_the_copy_shares_every_repo_file_unchanged(moe_root):
    for path in (REPO / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            copy = moe_root / path.relative_to(REPO)
            assert copy.read_bytes() == path.read_bytes(), path


def test_model_settings_reach_the_program(moe_root):
    cell = Bench(moe_root).cell("toy_moe.chat")
    mcfg = model_config(cell.config)
    assert (mcfg.family, mcfg.n_experts, mcfg.top_k, mcfg.moe_capacity_factor,
            mcfg.use_qk_norm, mcfg.d_ff) == ("moe", 4, 2, 2.0, True, 32)
    assert cell.family.__name__ == "bench_reference_toy_moe"


@pytest.mark.parametrize("seed", [2**31 + 9, 5000000011])
def test_a_new_family_runs_correct(moe_root, seed):
    r = run(moe_root, seed)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["widest_gap"]["value"] <= LIMIT


def test_a_new_family_catches_an_altered_token(moe_root):
    r = run(moe_root, 2**31 + 9, fault="token")
    assert r["correct"] is False
    assert r["checks"]["widest_gap"]["value"] > LIMIT


def test_a_new_family_fails_its_fp8_control(moe_root):
    r = run(moe_root, 1, control=True)
    c = r["checks"]
    assert r["correct"] is False
    assert c["program_gap"]["value"] <= LIMIT < c["widest_gap"]["value"]


def test_a_new_family_counts_its_own_flops(moe_root):
    r = run(moe_root, 4, traced=True)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0 < m["step_mfu.chat"] <= 100
    fam = Bench(moe_root).cell("toy_moe.chat").family
    dense = Bench(moe_root).reference("dense_transformer")
    # beside the dense layer without its MLP: the router and two SwiGLU experts a row
    D, L, F = TOY_MOE["d_model"], TOY_MOE["n_layers"], TOY_MOE["d_ff"]
    extra = (fam.call_flops(TOY_MOE, [(0, 16)], [5])
             - dense.call_flops(dict(TOY_MOE, d_ff=0), [(0, 16)], [5]))
    assert extra == 17 * L * 2 * (D * 4 + 2 * 3 * D * F)


def test_a_family_module_without_a_function_is_refused(moe_root):
    src = (moe_root / "bench/references/toy_moe.py").read_text()
    (moe_root / "bench/references/no_counts.py").write_text(
        src.replace("def call_bytes(", "def _call_bytes(")
        .replace("def leaf_std(", "def _leaf_std("))
    (moe_root / "bench/configs/no_counts.json").write_text(
        json.dumps(dict(TOY_MOE, reference="no_counts")))
    spec = json.loads((moe_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "no_counts.chat", "config": "no_counts",
                              "traffic": "tiny_chat", "chips": 1, "why": "test"})
    (moe_root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(SpecError, match="leaf_std, call_bytes"):
        Bench(moe_root).cell("no_counts.chat")
    assert {"leaf_std", "call_flops", "call_bytes"} <= set(FAMILY_API)


@pytest.mark.parametrize("settings,word", [({"n_expertz": 4}, "n_expertz"),
                                           ({"d_model": 128}, "d_model"),
                                           ({"name": "other"}, "name")])
def test_an_unknown_model_setting_is_refused(settings, word):
    with pytest.raises(SpecError, match=word):
        model_config(dict(TOY_MOE, model=settings))


def test_every_repo_configuration_builds_its_arch_unchanged():
    """The repository's configurations state the program's own sizes:
    the sizes and settings they set leave each arch's ModelConfig as it
    is, so the cells run the program's published configuration."""
    from repro.configs import get_config
    bench = Bench(REPO)
    for w in bench.all_cells():
        cfg = bench.cell(w).config
        assert model_config(cfg) == get_config(cfg["arch"]), w
    assert not bench.config("starcoder2-3b").get("model")
    assert len(MODEL_KEYS) == 9
