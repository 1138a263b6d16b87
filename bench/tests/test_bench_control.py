"""The control: the brute-force reference one precision step down
(bf16_3x dots where the configuration states float32) put in the
program's place must fail the check, on every seed tried."""
import json

import pytest

import _benchpaths  # noqa: F401
from benchlib import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, capsys):
    import runpy
    mod = runpy.run_path(str(spec.BENCH / "control.py"),
                         run_name="bench_control")
    rc = mod["main"](["--workload", cell, "--seeds", "1,2,3",
                      "--queries", "200", "--rehearse"])
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3
    for ln in lines:
        assert ln["correct"] is False, ln
        assert ln["checks"]["dist_err"]["value"] > \
            ln["checks"]["dist_err"]["limit"]
    assert rc == 0
