"""BENCHMARK.json against the shape the benchmark's readers rely on, and
every name in it resolved to its file."""
import re

import pytest

from chipbench import run

SPEC = run.load_json(run.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in SPEC["end_to_end"])


def test_names_units_and_lengths():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in SPEC["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_and_reports(w):
    cell = run.cell_from_spec(SPEC, w["name"], 1, 1.0, False)
    assert (run.HERE / "drivers" / f"{cell.traffic['kind']}.py").is_file()
    assert cell.traffic["limits"]
    run.costs_for(cell)
    e2e = [m["name"] for m in run.metrics_for(SPEC, w["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per = run.metrics_for(SPEC, w["name"], "per_layer")
    assert per
    for m in per:
        assert m["moves"] in e2e
        assert hasattr(run.load_module(
            run.HERE / "metrics" / f"{m['name']}.py"), "read")


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_every_config_file_matches_its_entry(c):
    cfg = run.load_json(run.ROOT / c["file"])
    assert cfg["name"] == c["name"]
    assert cfg["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])
