"""BENCHMARK.json is well formed and every name in it has its file."""
import json
import re

import pytest

import tiny
from harness import config, traffic

ROOT = tiny.BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run_cell.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    n = len(SPEC["workloads"])
    # a full check of 24 cells fits its time
    assert 2 + 14 * 24 * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, n // 2)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_one_line_texts():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in SPEC["workloads"] + SPEC["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_has_its_files(w):
    conf = config.load_json("configs", w["config"])
    mix = config.load_json("traffic", w["traffic"])
    cell = config.load_json("cells", w["name"])
    assert (tiny.BENCH / "reference" / f"{conf['reference']}.py").exists()
    sizes = config.model_sizes(conf)
    srv = cell["server"]
    # every request the mix can make fits one row of the page table
    need = mix["prompt"]["max"] + mix["output"]["max"] - 1
    assert need <= srv["max_len"]
    assert traffic.prefill_buckets(mix, 8, srv["max_len"])[-1] \
        <= srv["max_len"]
    assert sizes["n_layers"] >= 1 and cell["check"]["widest_gap"]["limit"] > 0
    # each cell reports setup_s, another end-to-end metric and a layer's
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(w["name"] in m.get("workloads", [w["name"]])
               for m in SPEC["per_layer"])


def test_every_metric_has_a_reader_and_moves_a_reported_metric():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").exists()
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
    assert 0 < e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


def test_config_files_name_what_they_change():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
