"""BENCHMARK.json and the files it names: every rule a later change could
break by appending an entry."""

import copy
import json
import shutil

import pytest

import chipbench_testlib as lib

from benchlib import manifest


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_manifest_is_sound(bench):
    assert manifest.validate(bench) == []


@pytest.mark.parametrize("bad", ["", "has space", "a,b", "a/b", "x" * 65,
                                 ".lead", "µs"])
def test_bad_names_refused(bench, bad):
    b = copy.deepcopy(bench)
    b["workloads"][0]["name"] = bad
    assert any("name" in e for e in manifest.validate(b))


@pytest.mark.parametrize("bad", ["tokens per second", "", "x" * 17, "µs"])
def test_bad_units_refused(bench, bad):
    b = copy.deepcopy(bench)
    b["per_layer"][0]["unit"] = bad
    assert any("unit" in e for e in manifest.validate(b))


def test_moves_must_be_reported_by_every_listed_cell(bench):
    b = copy.deepcopy(bench)
    other = b["workloads"][-1]["name"]
    b["end_to_end"].append({"name": "other_metric", "unit": "s",
                            "better": "lower", "bound": 0.1,
                            "source": "host_clock", "workloads": [other]})
    pl = next(m for m in b["per_layer"]
              if any(c != other for c in m.get("workloads", [])))
    pl["moves"] = "other_metric"
    assert any("does not report" in e for e in manifest.validate(b))


def test_moves_must_name_an_end_to_end_metric(bench):
    b = copy.deepcopy(bench)
    b["per_layer"][0]["moves"] = "setup_s"
    assert any("not an end-to-end metric" in e for e in manifest.validate(b))


def test_every_cell_file_exists(bench):
    for w in bench["workloads"]:
        c = manifest.cell(bench, w["name"])
        assert (lib.CHIP / "runners" / f"{c.traffic['runner']}.py").is_file()
        assert c.end_to_end and c.per_layer
    for m in bench["per_layer"]:
        assert (lib.CHIP / "metrics" / f"{m['name']}.py").is_file()


def test_missing_files_refused(bench):
    b = copy.deepcopy(bench)
    b["workloads"][0]["traffic"] = "no_such_traffic"
    b["configs"][0]["file"] = "benchmarks/chip/configs/none.json"
    errs = manifest.validate(b)
    assert any("traffic file" in e for e in errs)
    assert any("missing" in e and "config" in e for e in errs)


def test_config_files_keep_published_widths(bench):
    for c in bench["configs"]:
        with open(lib.ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"] == []
        from benchlib.lm import build_model
        cfg, _ = build_model(conf)
        assert cfg.d_model == conf["hidden_size"]


def test_new_traffic_file_and_cell_taken_up(bench, tmp_path):
    """A later change adds a traffic file and appends a cell: nothing else
    changes, and the harness runs the new cell."""
    chip = tmp_path / "chip"
    shutil.copytree(lib.CHIP / "traffic", chip / "traffic")
    shutil.copytree(lib.CHIP / "runners", chip / "runners")
    shutil.copytree(lib.CHIP / "metrics", chip / "metrics")
    tf = lib.traffic("round_sync", clients=4, tasks=3, check_rounds=1)
    (chip / "traffic" / "round_tiny.json").write_text(json.dumps(tf))
    b = copy.deepcopy(bench)
    b["workloads"].append({"name": "qwen2-0.5b.round_tiny",
                           "config": "qwen2-0.5b", "traffic": "round_tiny",
                           "chips": 1, "why": "tiny round"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "qwen2-0.5b.round_sync" in m.get("workloads", []):
            m["workloads"].append("qwen2-0.5b.round_tiny")
    assert manifest.validate(b, chip_dir=chip) == []
    c = manifest.cell(b, "qwen2-0.5b.round_tiny", chip_dir=chip)
    assert c.traffic["clients"] == 4
    c.config = dict(c.config, lora_d=4096)
    out = lib.run_cell(c, seconds=0.5)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"round_clients_per_s", "setup_s"}
