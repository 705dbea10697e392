"""A configuration, a traffic mix and a per-layer metric are found by name:
new files and new manifest entries, no edit to a file that exists."""

import json
import os

from test_rehearsal import ROOT, rehearsal_manifest, rehearse

READER = '''"""A reader dropped in by a test."""


def read(run):
    return float(run.calls)
'''


def test_dropped_in_files_are_found(tmp_path):
    base = tmp_path / "manifest"
    rehearsal_manifest(tmp_path)
    with open(base / "configs" / "tiny-lm.json") as f:
        config = json.load(f)
    config["num_hidden_layers"] = 1
    with open(base / "configs" / "dropped-lm.json", "w") as f:
        json.dump(config, f)
    with open(base / "traffic" / "tiny-2x128.json") as f:
        traffic = json.load(f)
    traffic["per_chip"] = 4
    with open(base / "traffic" / "dropped-4x128.json", "w") as f:
        json.dump(traffic, f)
    with open(base / "BENCHMARK.json") as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "dropped-lm", "source": "test",
                                "file": "configs/dropped-lm.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "dropped", "config": "dropped-lm",
                                  "traffic": "dropped-4x128", "chips": 1,
                                  "why": "test"})
    manifest["per_layer"].append({
        "name": "dropped_calls.lm", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "tokens_per_s", "workloads": ["dropped"]})
    with open(base / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    reader = os.path.join(ROOT, "benchmarks", "metrics", "dropped_calls.py")
    with open(reader, "w") as f:
        f.write(READER)
    try:
        result, stdout = rehearse("dropped", 1, 1, tmp_path / "out",
                                  manifest=str(base / "BENCHMARK.json"))
    finally:
        os.remove(reader)
    assert "parameters=0.1M" in stdout            # one layer, not two
    assert result["metrics"]["dropped_calls.lm"]["value"] \
        == result["attempted"]


def test_a_mix_that_extends_another_differs_in_its_own_keys_alone():
    from benchmarks.run import load_cell
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    *_, one_chip = load_cell(manifest, "dsc1p3b-s2048")
    *_, four_chips = load_cell(manifest, "dsc1p3b-dp4")
    assert "extends" not in four_chips
    assert four_chips["why"] != one_chip["why"]
    assert {**four_chips, "why": ""} == {**one_chip, "why": ""}
