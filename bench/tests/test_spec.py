"""``BENCHMARK.json`` and the files it names hold together: every name
finds its file, every per-layer metric moves an end-to-end metric its
cells report, and the run's cost fits the check's time."""
import os
import re

from bench import lib

SPEC = lib.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _exists(*parts):
    return os.path.exists(os.path.join(lib.BENCH, *parts))


def test_every_name_finds_its_files():
    for c in SPEC["configs"]:
        cfg = lib.load_json(os.path.relpath(os.path.join(lib.ROOT,
                                                         c["file"]),
                                            lib.BENCH))
        assert _exists("structures", cfg["structure"] + ".py")
    for w in SPEC["workloads"]:
        tr = lib.load_json("traffic", w["traffic"] + ".json")
        assert _exists("loops", tr["loop"] + ".py")
        assert _exists("cells", w["name"] + ".json")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert _exists("metrics", m["name"] + ".py"), m["name"]


def test_names_units_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = lib.cell_metrics(SPEC, w["name"], "end_to_end")
        per = lib.cell_metrics(SPEC, w["name"], "per_layer")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert per


def test_per_layer_metrics_move_what_their_cells_report():
    for m in SPEC["per_layer"]:
        for cell in m["workloads"]:
            e2e = {x["name"] for x in lib.cell_metrics(SPEC, cell,
                                                       "end_to_end")}
            assert m["moves"] in e2e, (m["name"], cell)


def test_a_full_check_of_24_cells_fits():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
