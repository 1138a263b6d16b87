"""The timed path broken underneath a rehearsal run: ``correct`` must
come out false, once for each fault a serving cell can have (there is no
training state and, on one chip, no exchange between chips)."""
import json
import subprocess
import sys
import textwrap

import pytest

import _benchpaths  # noqa: F401
from test_bench_rehearse import CELLS, ROOT, _env

# -- the timed path broken underneath: `correct` must come out false -----
FAULTS = {
    # half of each batch left out: the tick answers only its first half
    "half_batch": """
        def broken(self, qbatch, nlive):
            keep = nlive // 2
            d, g, t, s, dt = ORIG(self, qbatch, max(keep, 1))
            d[keep:], g[keep:] = PAD_DIST, -1
            return d, g, t, s, dt
    """,
    # an answer altered where it is produced: each id moved by one
    "altered_answer": """
        def broken(self, qbatch, nlive):
            d, g, t, s, dt = ORIG(self, qbatch, nlive)
            g[:nlive] = np.where(g[:nlive] >= 0, g[:nlive] + 1, -1)
            return d, g, t, s, dt
    """,
}


CAUGHT_BY = {"half_batch": "empty", "altered_answer": "dist_err"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    code = textwrap.dedent("""
        import json, runpy, sys
        import numpy as np
        mod = runpy.run_path(sys.argv[1], run_name="bench_run")
        from repro.core.refine import PAD_DIST
        from repro.fleet.engine import FleetEngine
        ORIG = FleetEngine._execute
    """) + textwrap.dedent(FAULTS[fault]) + textwrap.dedent("""
        FleetEngine._execute = broken
        args = mod["parse"](["--workload", sys.argv[2], "--rehearse",
                             "--seed", "5", "--seconds", "2"])
        out = mod["run"](args)
        print(json.dumps({"correct": out["line"]["correct"],
                          "checks": out["line"]["checks"]}))
    """)
    res = subprocess.run(
        [sys.executable, "-c", code, str(ROOT / "bench/run.py"), CELLS[0]],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=_env(tmp_path))
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out
    assert out["checks"][CAUGHT_BY[fault]]["value"] > \
        out["checks"][CAUGHT_BY[fault]]["limit"], out
