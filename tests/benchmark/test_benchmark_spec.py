"""The manifest and every data file load and cross-reference by name;
a cell added as files is found without a code change."""

import json
import os
import re

import pytest

from benchmark_tiny import REPO, make_root, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return spec.load_manifest()


def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(spec.MANIFEST) <= 64 * 1024
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    assert any(w.startswith(p + "/") for w in manifest["command"]
               for p in manifest["paths"])


def test_names_units_and_whys(manifest):
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section
        assert all(NAME.match(n) for n in names), names
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_end_to_end_bounds(manifest):
    metrics = {m["name"]: m for m in manifest["end_to_end"]}
    assert metrics["setup_s"]["bound"] == 0.1
    for m in metrics.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_four_chip_cells_are_at_most_a_quarter_or_one(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_cells_configs_and_traffic_cross_reference(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert sorted(w["name"] for w in manifest["workloads"]) == \
        spec.cell_names()
    used = set()
    for w in manifest["workloads"]:
        cell = spec.Cell(w["name"])
        assert (cell.config_name, cell.traffic_name, cell.chips) == \
            (w["config"], w["traffic"], w["chips"])
        assert cell.processes in (1, cell.chips)
        assert os.path.exists(cell.trainer_path)
        assert cell.workload["loss_band"]["step"] >= 1
        family = cell.config["family"]
        for sub in ("models", "reference"):
            assert os.path.exists(os.path.join(spec.HERE, sub,
                                               family + ".py"))
        assert len(cell.config["check_leaves"]) == 3
        used.add(w["config"])
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in configs.values():
        assert c["file"] == "benchmarks/configs/%s.json" % c["name"]
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_every_per_layer_metric_has_a_reader_that_agrees(manifest):
    readers = spec.metric_readers()
    end_to_end = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    # A reader without an entry waits for a cell that has what it reads.
    assert {m["name"] for m in manifest["per_layer"]} <= set(readers)
    for r in readers.values():
        assert r.read({}) is None   # nothing to read, nothing reported
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        r = readers[m["name"]]
        assert (r.LAYER, r.UNIT, r.BETTER, r.SOURCE, r.MOVES) == \
            (m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", [])) <= cells


def test_a_reader_that_finds_nothing_is_left_out(manifest):
    cell = manifest["workloads"][0]["name"]
    run = {"init_s": 1.5, "programs_compiled": 0,
           "device": {"memory_peak_bytes": 3 << 30}}
    got = spec.read_layer_metrics(run, cell, manifest)
    assert got == {"init_s": {"value": 1.5, "unit": "s"},
                   "programs_compiled": {"value": 0.0, "unit": "count"},
                   "peak_hbm_gib": {"value": 3.0, "unit": "GiB"}}


def test_metrics_limited_to_cells_are_read_only_there(manifest):
    run = {"trace": {"collective_s": 0.05, "busy_s": 1.0, "window_s": 1.1,
                     "idle_share": 0.1, "mxu_share": 0.9,
                     "attention_share": None},
           "traced_steps": 5}
    by_cell = {w["name"]: spec.read_layer_metrics(run, w["name"], manifest)
               for w in manifest["workloads"]}
    limited = [m for m in manifest["per_layer"] if "workloads" in m]
    assert limited
    for m in limited:
        for cell, got in by_cell.items():
            assert (m["name"] in got) == (cell in m["workloads"])
    four = by_cell[limited[0]["workloads"][0]]
    assert four["exposed_collective_ms"]["value"] == pytest.approx(10.0)
    assert four["step_device_ms"]["value"] == pytest.approx(200.0)
    assert four["device_idle_share"]["value"] == pytest.approx(10.0)
    assert "attention_share" not in four   # the trace carried no path


def test_the_eager_planes_readers():
    readers = spec.metric_readers()
    run = {"exchange_s": [0.1, 0.3, 0.2], "steps": 4,
           "responses_dispatched": 84.0, "step_s": [0.5] * 3,
           "local_step_s": [0.25] * 3}
    assert readers["exchange_ms"].read(run) == pytest.approx(200.0)
    assert readers["responses_per_step"].read(run) == 21.0
    assert readers["eager_efficiency"].read(run) == pytest.approx(50.0)
    assert readers["step_ms_p90"].read(run) is None   # under 20 samples


def test_a_cell_added_as_files_is_found_without_a_code_change(tmp_path):
    root = str(tmp_path)
    name = make_root(root, "gpt", "ingraph")
    assert spec.cell_names(root) == [name]
    cell = spec.Cell(name, root)
    assert cell.config["family"] == "gpt" and cell.config["n_embd"] == 64
    assert cell.traffic["seq_len"] == 32 and cell.processes == 1
    assert cell.trainer_path.endswith("trainers/ingraph.py")
    assert spec.load_family(cell.config).__name__.endswith("models.gpt")


def test_no_cell_or_configuration_name_in_the_benchmarks_code(manifest):
    names = [e["name"] for e in manifest["workloads"] + manifest["configs"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    for dirpath, _, files in os.walk(spec.HERE):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    text = fh.read()
                for n in names:
                    assert n not in text, (f, n)
