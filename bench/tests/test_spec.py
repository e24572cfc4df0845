"""BENCHMARK.json holds to its contract, and every cell finds its files."""
import dataclasses
import json
import re
from pathlib import Path

import pytest

from bench import harness, reference, traffic

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_entries_have_just_their_keys_and_valid_names():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in METRICS:
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if m in SPEC["end_to_end"] else {"layer", "moves"}
        assert set(m) <= allowed and NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"] + METRICS]
    assert len(names) == len(set(names))
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(CELLS)


def test_bounds_and_sources():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files(cell):
    wl, cfg, mix, limits = harness.resolve(SPEC, cell)
    assert harness.driver_class(cfg).__name__ == "Driver"
    assert mix["loop"] in ("open", "closed") and "max_dlogdet" in limits
    e2e = harness.metrics_for(SPEC, cell, traced=False)
    layer = harness.metrics_for(SPEC, cell, traced=True)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and layer
    for m in e2e + layer:
        if "workloads" in m:
            assert set(m["workloads"]) <= set(CELLS)
        harness.metric_module(m["name"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_reader_agrees_with_the_spec(metric):
    entry = next(m for m in METRICS if m["name"] == metric)
    mod = harness.metric_module(metric)
    assert mod.UNIT == entry["unit"] and mod.SOURCE == entry["source"]
    if "layer" in entry:
        assert (mod.LAYER, mod.MOVES) == (entry["layer"], entry["moves"])
    assert callable(mod.read)


def test_every_metric_file_is_a_reader():
    """Every file in bench/metrics/ is a reader that loads."""
    for path in (harness.BENCH / "metrics").glob("*.py"):
        mod = harness.metric_module(path.stem)
        assert callable(mod.read) and UNIT.match(mod.UNIT)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_files_state_their_preset_with_each_change_listed(cfg):
    from repro.configs import spdc as presets

    body = json.loads((ROOT / cfg["file"]).read_text())
    base = getattr(presets, body["preset"])
    spdc = base.spdc if hasattr(base, "spdc") else base
    changed = set(body.get("spdc_changes", {})) | set(body.get("gateway_changes", {}))
    assert changed == set(body["reduced"]) == set(cfg["reduced"])
    for key, value in body["spdc"].items():
        expect = body.get("spdc_changes", {}).get(key, getattr(spdc, key))
        assert value == expect, key
    if hasattr(base, "spdc"):
        for field, preset in body["presets"].items():
            assert getattr(base, field) == getattr(presets, preset), field
        for f in dataclasses.fields(base):
            if f.name in body and f.name != "spdc":
                got = body[f.name]
                assert (tuple(got) if isinstance(got, list) else got) == getattr(base, f.name)
    else:
        assert body["matrix_n"] == body.get("spdc_changes", {}).get("matrix_n", base.matrix_n)


@pytest.mark.parametrize("cell", CELLS)
def test_limits_lie_between_their_readings(cell):
    body = json.loads((reference.LIMITS_DIR / f"{cell}.json").read_text())
    lim = body["limits"]["max_dlogdet"]
    lower, upper = body["readings"]["program_max"], body["readings"]["control_min"]
    assert lower < lim < upper and upper >= 3 * lower


def test_traffic_files_are_data_the_generator_reads():
    for path in traffic.TRAFFIC_DIR.glob("*.json"):
        mix = traffic.load(path.stem)
        traffic.Schedule(mix, 1, 2, default_n=64)
