"""BENCHMARK.json, the configuration and traffic files, and discovery of
files by name."""
import json
import shutil

import pytest

import _benchpaths  # noqa: F401
from benchlib import spec, traffic


def test_benchmark_and_every_file_load():
    bm = spec.load_benchmark()
    spec.validate(bm)
    for c in bm["configs"]:
        cfg = spec.load_config(bm, c["name"])
        for key in c["reduced"]:
            assert key in cfg, f"{c['name']}: reduced key {key} not in file"
        for key in ("climber", "fleet", "serving", "corpus", "limits",
                    "rehearsal", "num_series"):
            assert key in cfg
    for w in bm["workloads"]:
        traffic.check_traffic(spec.load_traffic(w["traffic"]))
        assert spec.end_to_end_for(bm, w["name"])
        assert spec.per_layer_for(bm, w["name"])
        assert "setup_s" in [m["name"] for m in
                             spec.end_to_end_for(bm, w["name"])]


@pytest.mark.parametrize("name,ok", [
    ("rw256-k500-batch", True), ("batch_fill.paced", True), ("_x", True),
    ("a" * 64, True), ("a" * 65, False), ("has space", False),
    ("a/b", False), ("a,b", False), ("-lead", False), ("mü", False)])
def test_name_rule(name, ok):
    if ok:
        spec.check_name(name, "x")
    else:
        with pytest.raises(spec.SpecError):
            spec.check_name(name, "x")


@pytest.mark.parametrize("unit,ok", [
    ("queries/s", True), ("%", True), ("ms", True), ("share", True),
    ("tokens per s", False), ("µs", False), ("a" * 17, False)])
def test_unit_rule(unit, ok):
    assert bool(spec.UNIT_RE.match(unit)) is ok


def test_harness_finds_files_it_was_not_told_about(tmp_path):
    """A new configuration, traffic mix and metric need only new files and
    new BENCHMARK.json entries."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    bm = spec.load_benchmark()
    cfg = spec.load_config(bm, "rw256-paper")
    (root / "bench/configs/rw96-new.json").write_text(
        json.dumps(dict(cfg, name="rw96-new")))
    (root / "bench/workloads/rw96-trickle.json").write_text(json.dumps(
        {"mode": "closed", "outstanding": 2, "connections": 1, "pool": 50,
         "query_noise": 0.05}))
    (root / "bench/metrics/admission_wait_ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm["configs"].append({"name": "rw96-new", "source": "x",
                          "file": "bench/configs/rw96-new.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "rw96-trickle", "config": "rw96-new",
                            "traffic": "rw96-trickle", "chips": 1,
                            "why": "x"})
    bm["per_layer"].append({"name": "admission_wait_ms", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "admission", "moves": "qps",
                            "workloads": ["rw96-trickle"]})
    qps = [m for m in bm["end_to_end"] if m["name"] == "qps"][0]
    qps["workloads"].append("rw96-trickle")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    new = spec.load_benchmark(root)
    spec.validate(new, root)
    assert spec.load_config(new, "rw96-new", root)["name"] == "rw96-new"
    assert spec.load_traffic("rw96-trickle", root / "bench")["pool"] == 50
    assert [m["name"] for m in spec.per_layer_for(new, "rw96-trickle")] == \
        ["admission_wait_ms"]
    assert spec.load_reader("admission_wait_ms", root / "bench")(None) == 42


def test_missing_reader_is_an_error(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric", tmp_path)
