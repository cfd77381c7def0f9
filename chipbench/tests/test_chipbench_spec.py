"""BENCHMARK.json and the files it names: every cell resolves to its
files, every name and unit keeps to the allowed characters, and every
per-layer metric's ``moves`` is reported wherever the metric is."""

import json
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
LINE_RE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.load_cell(cell)
    assert c.chips == 1
    ref = spec.reference(c.config["reference"])
    dims = ref.dims(c.config)
    assert dims["vocab"] > 0
    assert c.traffic["loop"] == "closed_rounds"
    assert set(c.check["limits"]) >= {"max_logit_gap"}
    for m in spec.metrics_for(BENCH, cell, False) + \
            spec.metrics_for(BENCH, cell, True):
        assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_per_layer(cell):
    e2e = {m["name"] for m in spec.metrics_for(BENCH, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(BENCH, cell, True)


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["config"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert spec.NAME_RE.match(n), n
    for m in METRICS:
        assert spec.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        got = [x["name"] for x in group]
        assert len(got) == len(set(got))
    for text in ([w["why"] for w in BENCH["workloads"]]
                 + [c["why"] for c in BENCH["configs"]]
                 + [c["source"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert LINE_RE.match(text), text


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_moves_is_reported_wherever_the_metric_is(metric):
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        reported = {m["name"] for m in spec.metrics_for(BENCH, cell, False)}
        assert metric["moves"] in reported, (metric["name"], cell)


def test_bounds_within_contract():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m["name"]


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file_states_its_cuts(config):
    conf = json.loads((spec.ROOT / config["file"]).read_text())
    assert conf["source"] == config["source"]
    assert conf["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in conf
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_limit_rule():
    from chipbench.calibrate import limit
    # 60 % of the way from lower to upper on a log scale, 3 digits
    assert limit(0.1, 1.0) == float(f"{0.1 ** 0.4:.3g}") == 0.398
    assert limit(0.01, 0.25) == 0.069
    assert limit(1.0, 2.9) is None          # control under 3x the lower


@pytest.mark.parametrize("cell", CELLS)
def test_limits_follow_from_their_readings(cell):
    from chipbench.calibrate import limit
    c = spec.load_cell(cell)
    r = c.check["readings"]
    assert len(set(r["seeds"])) >= 12
    assert c.check["limits"]
    for number, lim in c.check["limits"].items():
        lo, hi = r[number]["lower"], r[number]["upper"]
        assert lim == limit(lo, hi) and lo < lim < hi
