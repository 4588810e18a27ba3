"""A cell is found by name; a later PR's cell, configuration, mix and per-layer
metric are new files plus appended entries; the manifest keeps to the
contract's limits; run.py refuses a machine without the chip."""

import filecmp
import json
import os
import re
import types

import pytest

import helpers
from benchmark import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files(manifest):
    for w in manifest["workloads"]:
        cell = cells.resolve(w["name"])
        assert cell.config["family"] == "gpt2" and cell.mix["kind"] in ("train", "serve")
        assert callable(cell.runner.run) and callable(cell.runner.readings)
        assert callable(cell.reference.init_params) and callable(cell.family.build_model)
        assert set(cell.limits) and "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        for metric in cell.per_layer:
            assert callable(cell.layer_metric(metric).read)
    with pytest.raises(KeyError):
        cells.resolve("no.such-cell")


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(tmp_path):
    root = helpers.fixture_repo(str(tmp_path))
    cell = cells.resolve("tiny.train", root)
    assert cell.config["n_embd"] == 128 and cell.mix["rows_per_step"] == 4
    assert "steps_in_window.train" in cell.per_layer
    assert cell.layer_metric("steps_in_window.train").read({"counters": {"steps": 5}}) == 5
    assert "train_tokens_per_s" in cell.end_to_end
    assert cells.resolve("tiny.serve", root).runner.__name__.endswith("serve")
    # every file the benchmark had is byte for byte what it was
    cmp = filecmp.dircmp(helpers.BENCH, os.path.join(root, "benchmark"), ignore=["out", "tests", "__pycache__"])
    stack = [cmp]
    while stack:
        d = stack.pop()
        assert not d.diff_files and not d.left_only, (d.diff_files, d.left_only)
        stack.extend(d.subdirs.values())


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 4)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in manifest[group]]
        assert len(seen) == len(set(seen)) and all(NAME.match(n) for n in seen)
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", names))
        assert os.path.isfile(os.path.join(helpers.BENCH, "layer_metrics", m["name"] + ".py"))
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/") and c["reduced"] == []
    for w in manifest["workloads"]:
        assert len(w["why"]) <= 200 and w["config"] in {c["name"] for c in manifest["configs"]}
        assert any(w["name"] in m["workloads"] for m in manifest["per_layer"])


def test_a_serve_cell_lists_what_its_runner_measures_and_its_layers_move_that(manifest):
    """A percentile that a benchmark PR moves from ``end_to_end`` to ``per_layer``
    (or back) must leave no cell asking the runner for a metric it does not
    compute, and no per-layer metric moving one the cell does not report."""
    serve = [cells.resolve(w["name"]) for w in manifest["workloads"]]
    serve = [cell for cell in serve if cell.mix["kind"] == "serve"]
    assert serve
    moves = {m["name"]: m["moves"] for m in manifest["per_layer"]}
    zero = dict.fromkeys(("decode_tokens", "admitted", "steps", "occupancy_sum", "decode_syncs"), 0)
    at = {"t": 1.0, "stats": zero, "queue_depth": 0}
    idle = {"t0": 0.0, "closed": at, "untraced": at, "before": zero, "rids": [], "late_ms": [], "steps": []}
    for cell in serve:
        service = types.SimpleNamespace(results={}, recompile_events=0)
        metrics, _, _ = cell.runner.measure(cell, service, [], idle)  # a window with no request
        assert set(cell.end_to_end) <= set(metrics) | {"setup_s"}, cell.name
        assert {moves[name] for name in cell.per_layer} <= set(cell.end_to_end), cell.name


def test_run_refuses_a_machine_without_the_chip(capsys):
    import run as bench_run

    assert os.environ.get("JAX_PLATFORMS") == "cpu", "run these tests with JAX_PLATFORMS=cpu"
    rc = bench_run.main(["--workload", "gpt2-medium.train-1k", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 3 and out.out == "" and "needs 1 tpu chip" in out.err
