"""The harness finds a configuration, a mix and a metric by name, refuses
an unknown name, and refuses to run without a chip."""

import json
import os
import shutil

import pytest

from bench import harness

ROOT = harness.ROOT


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark's data files: BENCHMARK.json, configs, mixes
    and metric readers."""
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("configs", "mixes", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", sub),
                        root / "bench" / sub)
    return root


@pytest.mark.parametrize("workload", [
    w["name"] for w in harness.load_spec()["workloads"]])
def test_every_cell_resolves(workload):
    spec = harness.load_spec()
    cell = harness.resolve(workload)
    entry = harness.by_name(spec["workloads"], workload, "workload")
    assert cell.config["name"] == entry["config"]
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert all(callable(r) for r in cell.per_layer.values())
    for m in spec["per_layer"]:
        assert (m["name"] in cell.per_layer) == (workload in m["workloads"])
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def test_configs_set_no_execution_field():
    from repro.core.config import MiningConfig

    for entry in harness.load_spec()["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert set(cfg["mining"]) == {"delta", "l_max", "omega"}
        assert MiningConfig(**cfg["mining"]).backend == MiningConfig().backend
        assert cfg["reduced"] == entry["reduced"]
        assert cfg["source"] == entry["source"]


def test_new_config_mix_and_metric_are_found_by_name(tree):
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    cfg = json.loads((tree / "bench/configs/email-eu.json").read_text())
    cfg["name"] = "email-eu-small"
    cfg["generator"]["params"]["n_edges"] = 1000
    (tree / "bench/configs/email-eu-small.json").write_text(json.dumps(cfg))
    (tree / "bench/mixes/batch-cold.json").write_text(json.dumps(
        {"driver": "batch", "warmup_mines": 0}))
    (tree / "bench/metrics/answers.batch.py").write_text(
        "def read(ctx):\n    return ctx.n_answers\n")
    spec["configs"].append({**spec["configs"][0], "name": "email-eu-small",
                            "file": "bench/configs/email-eu-small.json"})
    spec["workloads"].append({"name": "email-eu-small.batch-cold",
                              "config": "email-eu-small",
                              "traffic": "batch-cold", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "answers.batch", "unit": "mines",
                              "better": "higher", "source": "host_clock",
                              "layer": "entry", "moves": "mine_edges_per_s",
                              "workloads": ["email-eu-small.batch-cold"]})
    for m in spec["end_to_end"]:
        if m["name"] == "mine_edges_per_s":
            m["workloads"].append("email-eu-small.batch-cold")
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve("email-eu-small.batch-cold", root=str(tree))
    assert cell.config["generator"]["params"]["n_edges"] == 1000
    assert cell.mix == {"driver": "batch", "warmup_mines": 0}
    assert cell.driver.__name__ == "bench.drivers.batch"
    assert list(cell.per_layer) == ["answers.batch"]
    assert cell.per_layer["answers.batch"](
        harness.LayerContext([], {}, 7, [1.0] * 7, None)) == 7
    assert cell.end_to_end == ["mine_edges_per_s", "setup_s"]
    assert cell.units["answers.batch"] == "mines"


@pytest.mark.parametrize("edit, message", [
    (lambda s: s["workloads"][0].update(config="nope"), "configuration"),
    (lambda s: s["workloads"][0].update(traffic="nope"), "traffic mix"),
    (lambda s: s["per_layer"][0].update(name="nope.batch"),
     "metric reader"),
])
def test_unknown_names_are_refused(tree, edit, message):
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    edit(spec)
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))
    first = spec["workloads"][0]["name"]
    with pytest.raises(harness.UnknownName, match=message):
        harness.resolve(first, root=str(tree))


def test_unknown_workload_driver_and_generator_are_refused(tree):
    with pytest.raises(harness.UnknownName, match="workload"):
        harness.resolve("no-such.cell", root=str(tree))
    (tree / "bench/mixes/batch.json").write_text(json.dumps(
        {"driver": "nope"}))
    with pytest.raises(harness.UnknownName, match="drivers"):
        harness.resolve("email-eu.batch", root=str(tree))
    with pytest.raises(harness.UnknownName, match="identifier"):
        harness._module("generators", "no-such")


def test_no_chip_exits_without_a_result(capsys):
    # JAX sees only the CPU here, so the run stops before any work
    rc = harness.main(["--workload", "email-eu.batch", "--seed", "1",
                       "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 3 and out == "" and "no accelerator" in err


def test_unknown_workload_exits_without_a_result(capsys):
    rc = harness.main(["--workload", "nope", "--seed", "1", "--seconds",
                       "1"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "no workload" in err
