"""BENCHMARK.json keeps to the shape the benchmark's runner expects."""
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def reported(b, metric, cell):
    m = next(x for x in b["end_to_end"] if x["name"] == metric)
    return cell in m.get("workloads", [w["name"] for w in b["workloads"]])


def test_names_units_and_keys(bench_json):
    b = bench_json
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in b[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_metric_and_a_layer(bench_json):
    b = bench_json
    for w in b["workloads"]:
        cell = w["name"]
        e2e = [m["name"] for m in b["end_to_end"] if reported(b, m["name"], cell)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        layers = [m for m in b["per_layer"] if cell in m["workloads"]]
        assert layers, cell


def test_per_layer_metrics_move_a_metric_their_cells_report(bench_json):
    b = bench_json
    for m in b["per_layer"]:
        for cell in m["workloads"]:
            assert reported(b, m["moves"], cell), (m["name"], cell)


def test_every_model_step_has_an_mfu_beside_its_rooflines(bench_json):
    b = bench_json
    for m in b["per_layer"]:
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert any("mfu" in x["name"] and x["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(x["workloads"])
                       for x in b["per_layer"]), m["name"]
