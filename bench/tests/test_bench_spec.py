import json
import re

import pytest

from bench import spec

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("wl", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_by_name(wl):
    cell = spec.load_cell(wl)
    assert cell.config["name"] in {c["name"] for c in BENCH["configs"]}
    assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_unknown_workload_and_metric_are_errors():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no.such_metric")


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_benchmark_file_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}
    for c in BENCH["configs"]:
        f = spec.load_json(spec.ROOT / c["file"])
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    assert len(json.dumps(BENCH)) < 64 * 1024
