"""A throw-away copy of the benchmark with a tiny configuration, tiny mixes and
cells of its own added as NEW files and APPENDED entries: what a later PR does.
Nothing that is there is edited, which is the point the resolution test makes.
"""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
TINY = os.path.join(HERE, "fixtures", "tiny")

TINY_LIMITS = {
    # between what the program and the fp8 control read at this size on the CPU
    # (seed 2**31 + 4242: gradient 0.0031 against 0.0152, update 0.0082 against
    # 0.0258; serve, seed 11: 0 against 0.0166); limits for these tests only.
    # The fixture's control is the reference in fp8 for both runners: a model
    # this small has no outliers for int8's even grid to lose (int8 reads 0).
    "tiny.train": {"loss_gap": 1e-3, "grad_norm_gap": 0.007, "update_norm_gap": 0.015,
                   "nonfinite_window_losses": 0},
    "tiny.fsdp4": {"loss_gap": 1e-3, "grad_norm_gap": 0.007, "update_norm_gap": 0.015,
                   "nonfinite_window_losses": 0},
    "tiny.serve": {"served_logit_gap": 0.005, "unfinished_requests": 0},
}


def fixture_repo(tmp: str) -> str:
    """``<tmp>/repo`` holding BENCHMARK.json and benchmark/ with the tiny cells
    appended.  Returns the repo root."""
    root = os.path.join(tmp, "repo")
    shutil.copytree(
        BENCH, os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("out", "tests", "__pycache__"),
    )
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    bench = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(TINY, "config.gpt2-tiny.json"), os.path.join(bench, "configs", "gpt2-tiny.json"))
    for mix in ("train-tiny", "fsdp4-tiny", "serve-tiny"):
        shutil.copy(os.path.join(TINY, f"traffic.{mix}.json"), os.path.join(bench, "traffic", f"{mix}.json"))
    for cell, limits in TINY_LIMITS.items():
        with open(os.path.join(bench, "limits", f"{cell}.json"), "w") as f:
            json.dump({"limits": limits}, f)
    manifest["configs"].append({
        "name": "gpt2-tiny", "source": "test fixture", "reduced": [], "why": "test",
        "file": "benchmark/configs/gpt2-tiny.json",
    })
    new_cells = {"tiny.train": ("train-tiny", 1), "tiny.fsdp4": ("fsdp4-tiny", 4), "tiny.serve": ("serve-tiny", 1)}
    for name, (mix, chips) in new_cells.items():
        manifest["workloads"].append(
            {"name": name, "config": "gpt2-tiny", "traffic": mix, "chips": chips, "why": "test"}
        )
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" not in metric:
            continue
        kinds = {json.load(open(os.path.join(bench, "traffic", w["traffic"] + ".json")))["kind"]
                 for w in manifest["workloads"] if w["name"] in metric["workloads"]}
        for name, (mix, _) in new_cells.items():
            if json.load(open(os.path.join(bench, "traffic", mix + ".json")))["kind"] in kinds:
                metric["workloads"].append(name)
    # a per-layer metric of the later PR's own: one new file, one new entry
    with open(os.path.join(bench, "layer_metrics", "steps_in_window.train.py"), "w") as f:
        f.write('"""Steps the window completed."""\n\n\ndef read(ctx):\n    return ctx["counters"].get("steps")\n')
    manifest["per_layer"].append({
        "name": "steps_in_window.train", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "train_tokens_per_s",
        "workloads": ["tiny.train"],
    })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
