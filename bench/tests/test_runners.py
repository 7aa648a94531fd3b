"""The runner that a configuration names, on the CPU: the mode-A cell
takes the mode-A runner; a runner that the test supplies as a module of
its own (``llm_runner.py``, FedCD mode B through ``FedLLMTrainer`` at a
reduced DeepSeek-V3) runs a cell through the shared window of
``run.run_cell``, and the readers of the harness's spans read it; an
unknown runner is refused with the key and the file named. Run with
``python -m pytest -q bench/tests``."""
from __future__ import annotations

import copy
import json
import sys

import pytest

from bench import run
from bench.runners import fedcd_fused
from bench.tests import llm_runner

SEED = 3_000_000_019


def test_mode_a_cell_resolves_to_the_mode_a_runner():
    spec = run.load_cell("c10x100_fedsgd")
    assert "runner" not in spec["config"]
    assert run.runner_of(spec) is fedcd_fused


def test_unknown_runner_names_the_key_and_the_file():
    spec = copy.deepcopy(run.load_cell("c10x100_fedsgd"))
    spec["config"]["runner"] = "no_such_runner"
    with pytest.raises(run.BadCell) as e:
        run.runner_of(spec)
    msg = str(e.value)
    assert '"runner"' in msg and "no_such_runner" in msg
    assert "bench/configs/fedcd_cifar_c10x100_fedsgd.json" in msg


def test_unknown_runner_makes_run_py_exit_non_zero(tmp_path, monkeypatch,
                                                   capsys):
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cfg = bench["configs"][0]
    config = json.loads((run.ROOT / cfg["file"]).read_text())
    config["runner"] = "no_such_runner"
    (tmp_path / cfg["file"]).parent.mkdir(parents=True)
    (tmp_path / cfg["file"]).write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    cell = bench["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(SEED),
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert '"runner"' in err and cfg["file"] in err


# a cell of mode B built by the test: one model row of a reduced
# DeepSeek-V3, four clients, FedSGD traffic
LLM_CONFIG = {"runner": "llm_test", "arch": "deepseek-v3-671b",
              "clients": 4, "per_client": 2, "seq": 16,
              "fed": {"n_devices": 4, "devices_per_round": 4,
                      "score_window": 2, "max_models": 1, "lr": 0.05}}
LLM_TRAFFIC = {"milestones": [], "compare_rounds": 1, "warm_rounds": 2}
SPAN_METRICS = ("plan_ms", "host_gap_ms")


@pytest.fixture
def llm_cell(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.runners.llm_test", llm_runner)
    base = run.load_cell("c10x100_fedsgd")
    return {"cell": {"name": "llm_test", "chips": 1},
            "config_file": "bench/tests/test_runners.py",
            "config": LLM_CONFIG, "traffic": LLM_TRAFFIC,
            "limits": {"programs": 0, "nonfinite_loss": 0},
            # the span readers go on the --trace 0 line here: a CPU run
            # has no device trace to run --trace 1 on
            "end_to_end": base["end_to_end"] + [
                m for m in base["per_layer"] if m["name"] in SPAN_METRICS],
            "per_layer": []}


def test_a_supplied_runner_runs_mode_b_through_the_shared_window(llm_cell):
    out = run.run_cell(llm_cell, SEED, 1.0, False, require_chip=False,
                       log=lambda *a: None)
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(metrics) == {"round_s", "train_samples_per_s", "setup_s",
                            *SPAN_METRICS}
    assert all(v > 0 for v in metrics.values()), metrics
    assert out["correct"] is True, out["compared"]
    assert out["compiles_in_window"] == 0
    assert out["attempted"] >= 2


def test_a_runner_that_cannot_build_its_system_raises(llm_cell):
    llm_cell["config"] = dict(LLM_CONFIG, arch="no-such-arch")
    with pytest.raises(KeyError, match="no-such-arch"):
        run.run_cell(llm_cell, SEED, 1.0, False, require_chip=False,
                     log=lambda *a: None)


def test_the_harness_counts_compared_rounds_the_window_did_not_run():
    programs = {1: "a", 2: "a", 3: "b", 4: "a", 5: "a"}
    assert run.stray_programs(programs, [1, 2, 3], [4, 5]) == 1.0
    assert run.stray_programs(programs, [1, 2], [4, 5]) == 0.0
