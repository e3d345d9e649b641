"""Every cell, configuration, generator, reference and metric named in
``BENCHMARK.json`` resolves by name to files under ``bench/``, and a new
one is found without editing any file that exists."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = sorted(w["name"] for w in BENCH["workloads"])


def test_every_cell_has_its_files():
    for w in BENCH["workloads"]:
        assert (ROOT / "bench" / "workloads" / f"{w['name']}.json").is_file(), w["name"]
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/"), c["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    gen = harness.load_module("traffic", c.workload["generator"])
    assert callable(gen.build)
    ref = harness.load_module("references", c.config["reference"])
    assert callable(ref.compare)
    assert c.config["call"]["fn"] == "repro.sort"
    assert callable(harness.resolve(c.config["call"]["fn"]))
    assert set(c.config["limits"]) and all(v >= 0 for v in c.config["limits"].values())
    for key in ("check_sorts", "trace_sorts"):
        assert int(c.workload[key]) >= 1
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
    names += [w["config"] for w in BENCH["workloads"]] + [w["traffic"] for w in BENCH["workloads"]]
    names += [r for c in BENCH["configs"] for r in c["reduced"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in BENCH[group]]
        assert len(got) == len(set(got)), group


def test_every_per_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = e2e[m["moves"]].get("workloads", cells)
            assert cell in reported, (m["name"], cell)


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for cell in CELLS:
        c = harness.load_cell(cell)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert sum(c == 4 for c in chips) <= max(1, len(chips) // 2)


def test_bounds_and_run_seconds():
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")["bound"] <= 0.25


def test_configuration_files_hold_their_sizes():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg.get("reduced_why", {}), key


def test_a_new_cell_is_found_by_its_files_alone(tmp_path):
    """Copy the tree, add a cell, a generator, a reference and a metric as
    new files plus entries in BENCHMARK.json, and load them by name."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "pgxd_kv32.flat", "config": "pgxd_kv32",
                               "traffic": "flat", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "flat.count", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "test",
                               "moves": "sorted_gb_per_s", "workloads": ["pgxd_kv32.flat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "workloads" / "pgxd_kv32.flat.json").write_text(json.dumps(
        {"generator": "flat", "traffic": {"value": 7}, "check_sorts": 1,
         "trace_sorts": 1}))
    (root / "bench" / "traffic" / "flat.py").write_text(
        "def build(params, *, n, sharding):\n    return lambda key: params['value']\n")
    (root / "bench" / "metrics" / "flat.count.py").write_text(
        "def read(run):\n    return 3\n")
    c = harness.load_cell("pgxd_kv32.flat", root=root)
    assert [m["name"] for m in c.per_layer][-1] == "flat.count"
    assert harness.load_module("traffic", "flat", root=root).build({"value": 7}, n=1,
                                                                   sharding=None)(0) == 7
    assert harness.load_module("metrics", "flat.count", root=root).read(None) == 3
