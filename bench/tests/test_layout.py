"""``BENCHMARK.json`` against the benchmark's contract, every name resolved
to its file, and a new configuration, traffic mix and per-layer metric
added as new files and entries alone."""
import json
import re
import shutil

import pytest

from benchlib import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_entries_follow_the_contract():
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in names
            names.add(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"])
                assert entry["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.load_cell(workload)
    ex = harness.executor_class(cell.traffic)
    for fn in ("start", "call", "free", "check"):
        assert callable(getattr(ex, fn))
    for fn in ("program_model", "reference_init", "reference_apply",
               "sample_flops"):
        assert callable(getattr(cell.model, fn))
    limits = cell.conf["check"]["limits"]
    assert set(limits) == {"loss_gap", "first_loss_gap", "acc_gap",
                           "state_gap"}
    assert {m["name"] for m in cell.end_to_end} == {"rounds_per_s",
                                                    "setup_s"}
    for m in cell.per_layer:
        assert callable(harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py").read)


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_configuration(config):
    conf = json.loads((harness.ROOT / config["file"]).read_text())
    assert conf["name"] == config["name"]
    assert conf["source"] == config["source"]
    assert conf["reduced"] == config["reduced"]
    assert conf["topology"]["num_clients"] == int(
        re.search(r"_k(\d+)$", config["name"]).group(1))


def test_additions_need_no_edit(tmp_path, monkeypatch):
    """A copy of the benchmark gains a configuration, a traffic mix with an
    executor of its own and a per-layer metric from new files and new
    entries only."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    bench = tmp_path / "bench"
    conf = json.loads((bench / "configs" / "mnist_mlp_k50.json").read_text())
    conf["name"] = "mnist_mlp_k20"
    conf["topology"]["num_clients"] = 20
    (bench / "configs" / "mnist_mlp_k20.json").write_text(json.dumps(conf))
    shutil.copy(bench / "configs" / "mnist_mlp_k50.py",
                bench / "configs" / "mnist_mlp_k20.py")
    mix = json.loads((bench / "traffic" / "scan.json").read_text())
    mix["scenario"] = "mobile-fading"
    mix["executor"] = "run_rounds_fading"
    (bench / "traffic" / "fading.json").write_text(json.dumps(mix))
    (bench / "executors" / "run_rounds_fading.py").write_text(
        (bench / "executors" / "run_rounds.py").read_text()
        + "\nExecutor.marker = 'fading'\n")
    (bench / "metrics" / "window_calls.py").write_text(
        "def read(run):\n    return float(run.rounds)\n")
    spec["configs"].append({"name": "mnist_mlp_k20", "source": "x",
                            "file": "bench/configs/mnist_mlp_k20.json",
                            "reduced": ["num_clients"], "why": "x"})
    spec["workloads"].append({"name": "mnist_mlp_k20.fading",
                              "config": "mnist_mlp_k20",
                              "traffic": "fading", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "window_calls", "unit": "rounds",
                              "better": "higher", "source": "host_clock",
                              "layer": "executor", "moves": "rounds_per_s",
                              "workloads": ["mnist_mlp_k20.fading"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", bench)
    cell = harness.load_cell("mnist_mlp_k20.fading")
    assert cell.conf["topology"]["num_clients"] == 20
    assert cell.traffic["scenario"] == "mobile-fading"
    assert harness.executor_class(cell.traffic).marker == "fading"
    assert [m["name"] for m in cell.per_layer][-1] == "window_calls"
    assert "window_calls" not in [
        m["name"] for m in harness.load_cell("mnist_mlp_k50.scan").per_layer]
