"""run.py end to end on the CPU (``--rehearse``): every cell of
BENCHMARK.json prints the contract's last line, and a cell, a mix and a
per-layer metric dropped in as new files are found without editing a
file that was there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(root, workload, trace, *extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", "5", "--seconds", "2",
         "--trace", str(trace), "--rehearse", *extra],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in cells()["workloads"]])
def test_every_cell_rehearses(cell, trace):
    bench = cells()
    out = rehearse(ROOT, cell, trace)
    assert set(out) == RESULT_KEYS  # no trace reduction on the CPU
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = next(w for w in bench["workloads"] if w["name"] == cell)
    assert out["device"]["platform"] == "cpu"  # named, never a chip's
    assert out["device"]["rehearsal"] is True
    assert out["device"]["count"] == want["chips"]
    listed = {m["name"]: m for m in
              bench["per_layer" if trace else "end_to_end"]}
    assert out["metrics"] and set(out["metrics"]) <= set(listed)
    for name, m in out["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["unit"] == listed[name]["unit"]
        assert "workloads" not in listed[name] \
            or cell in listed[name]["workloads"]
    if trace == 0:
        assert {"setup_s", "audio_s_per_s_chip"} <= set(out["metrics"])
        assert all(m["value"] > 0 for m in out["metrics"].values())
    else:
        assert out["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", ["ds2_streaming.voice_sparse"])
def test_cells_kept_for_later_still_run(cell):
    """A mix with no entry in BENCHMARK.json runs as <config>.<mix>."""
    out = rehearse(ROOT, cell, 0)
    assert out["correct"] is True and out["attempted"] > 0


def test_without_a_tpu_there_is_no_result():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cells()["workloads"][0]["name"], "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{")
                   for line in proc.stdout.splitlines())


def test_new_cell_mix_and_metric_are_only_new_files(tmp_path):
    """What a later PR does: new files, appended entries, no edit."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "deepspeech_tpu"),
               os.path.join(root, "deepspeech_tpu"))
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    bench = cells()
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "train_16s_b32.json")) as f:
        mix = json.load(f)
    mix["what"] = "short utterances, a new mix"
    mix["rehearsal"]["valid_frames"] = [40, 60]
    with open(os.path.join(root, "benchmark", "traffic",
                           "train_short.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "steps_in_window.py"), "w") as f:
        f.write('"""Steps completed in the window."""\n'
                'DRIVERS = ("train",)\n\n\n'
                'def read(record):\n    return record["units"]\n')
    bench["workloads"].append({
        "name": "ds2_full.train_short", "config": "ds2_full",
        "traffic": "train_short", "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "audio_s_per_s_chip",
        "workloads": ["ds2_full.train_short"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    out = rehearse(root, "ds2_full.train_short", 1)
    assert out["correct"] is True
    assert out["metrics"]["steps_in_window"]["value"] == out["attempted"]
    # The new metric is that cell's alone.
    other = rehearse(root, bench["workloads"][0]["name"], 1)
    assert "steps_in_window" not in other["metrics"]
    for path, body in before.items():
        with open(path, "rb") as f:
            assert f.read() == body, f"{path} was edited"
