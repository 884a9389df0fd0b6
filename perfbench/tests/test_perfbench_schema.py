"""BENCHMARK.json against the benchmark's contract, and a cell added by
files alone."""
import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from fleet_pending import bench_with_fleet, fleet_cell  # noqa: E402
from harness.common import load_cell, load_json, load_module  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_top_level(bench):
    assert set(bench) == KEYS
    assert bench["command"][:2] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench"]
    assert 1 <= bench["run_seconds"] <= 51
    cells = 24
    runs = 2 + 14 * cells
    budget = runs * (bench["run_seconds"] + 60) + cells * 180 + 1200
    assert budget <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
            if "better" in e:
                assert e["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_bounds(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_cells_report_what_their_metrics_move(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]

    def reports(metric, cell):
        return cell in e2e[metric].get("workloads", cells)

    for cell in cells:
        assert reports("setup_s", cell)
        assert any(reports(m, cell) for m in e2e if m != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(m["moves"], cell), \
                (m["name"], cell)


def test_chips_and_configs(bench):
    chips = [w["chips"] for w in bench["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/")
        cfg = load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_every_name_has_its_files(bench):
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        driver = os.path.join(BENCH, "drivers", cell.mix["kind"] + ".py")
        assert os.path.exists(driver)
        assert hasattr(cell.reference, "init_params")
        assert cell.limits
    for m in bench["per_layer"]:
        mod = load_module(os.path.join(BENCH, "metrics",
                                       m["name"] + ".py"))
        assert callable(mod.read)


def test_the_held_back_fleet_entries_keep_the_contract():
    """With the fleet cell's entries added, the file still keeps every
    rule above, and every name the entries use has its file."""
    merged = bench_with_fleet()
    test_top_level(merged)
    test_names_and_units(merged)
    test_bounds(merged)
    test_cells_report_what_their_metrics_move(merged)
    test_chips_and_configs(merged)
    cell = fleet_cell()
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       cell.mix["kind"] + ".py"))
    assert hasattr(cell.reference, "init_params") and cell.limits
    assert {m["name"] for m in cell.per_layer} >= {
        "grad_eval_ms_per_round", "flat_ms_per_round",
        "delta_sgd_step_ms_per_round", "round_tail_ms_per_round"}
    for m in cell.per_layer:
        mod = load_module(os.path.join(BENCH, "metrics",
                                       m["name"] + ".py"))
        assert callable(mod.read)


def test_fleet_files_match_the_program():
    """The fleet cell's configuration file holds the sizes the program's
    paper-task MLP has, its traffic the fleet_zipf preset's values, and
    its limits an exact comparison of cohorts and arena rows."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.paper_tasks import MLP_SMALL
    from repro.federation import get_scenario
    cell = fleet_cell()
    cfg, mix = cell.config, cell.mix
    assert cfg["program"]["config"] == "MLP_SMALL"
    assert (cfg["input_dim"], tuple(cfg["hidden_dims"]),
            cfg["num_classes"]) == (MLP_SMALL.input_dim,
                                    MLP_SMALL.hidden_dims,
                                    MLP_SMALL.num_classes)
    preset = get_scenario(mix["scenario"]["preset"])
    for k in ("scheduler", "zipf_s", "speed", "k_min_frac",
              "aggregation"):
        assert getattr(preset, k) == mix["scenario"][k], k
    assert mix["registered"] == preset.registered_hint
    assert mix["participation"] == preset.participation_hint
    assert mix["alpha"] == preset.alpha
    assert round(mix["participation"] * mix["registered"]) == 50
    assert mix["samples_per_partition"] // mix["batch"] == 7
    assert cell.limits["cohort"] == 0 and cell.limits["arena"] == 0
    assert {"loss", "eta", "change"} <= set(cell.limits)


def test_a_cell_added_by_files_alone(tmp_path):
    """A later change adds a traffic mix, the cell's limits and one
    entry in BENCHMARK.json; the harness finds them by name with no
    code changed."""
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mix = load_json(os.path.join(BENCH, "traffic", "fedtune4.json"))
    mix["local_steps"] = 1
    (copy / "perfbench" / "traffic" / "dummy_short.json").write_text(
        json.dumps(mix))
    name = "lm.whisper_tiny.dummy_short"
    (copy / "perfbench" / "cells" / (name + ".json")).write_text(
        json.dumps({"limits": {"loss": 1.0}}))
    bench["workloads"].append({"name": name, "config": "whisper_tiny",
                               "traffic": "dummy_short", "chips": 1,
                               "why": "a cell added by data alone"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell(name, bench_dir=str(copy / "perfbench"))
    assert cell.mix["local_steps"] == 1 and cell.limits == {"loss": 1.0}
    assert cell.config["d_model"] == 384
    assert os.path.exists(copy / "perfbench" / "drivers" /
                          (cell.mix["kind"] + ".py"))


def _run(cwd, env_extra=None):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "lm.whisper_tiny.fedtune4", "--seed", str(2 ** 31 + 1),
         "--seconds", "10", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run(str(tmp_path))
    assert out.returncode != 0 and out.stdout.strip() == ""
