"""BENCHMARK.json keeps to the contract's shapes, and every name it holds
finds its files."""
import json
import re

from benchlib import harness

B = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_names_and_units():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in B[key]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.fullmatch(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in B["per_layer"]:
        if m["name"].endswith("_ms"):
            assert m["unit"] == "ms", m
        if "roofline" in m["name"] or "share" in m["name"]:
            assert m["unit"] == "%", m


def test_every_cell_reports_what_its_layer_metrics_move():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = [w["name"] for w in B["workloads"]]
    for w in cells:
        mine = [m for m in B["end_to_end"] if w in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
    for m in B["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", cells), (m["name"], w)


def test_every_name_finds_its_files():
    for w in B["workloads"]:
        files = harness.cell_files(w["name"])
        assert set(files["limits"]) <= {"topk_gap", "score_gap",
                                        "ids_not_once"}
        assert files["limits"]
    for m in B["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
