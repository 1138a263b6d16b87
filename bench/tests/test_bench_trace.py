"""The reduction of a profiler trace to busy/idle time, scope time, top
operations and idle gaps by host span."""
from pathlib import Path

import pytest

import _benchpaths  # noqa: F401
from benchlib import trace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_length_merges_overlaps():
    total, merged = trace.union_length([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert total == 5
    assert merged == [[0, 3], [5, 7]]


def test_reduce_by_hand():
    ev = {"host": [("bench.window", 0.0, 10.0),
                   ("bench.tick", 1.0, 4.0),
                   ("bench.fleet_query", 1.5, 3.9),
                   ("fleet.mesh.query", 1.6, 3.8)],
          "devices": {"/device:TPU:0": [
              (2.0, 3.0, "jit_f/climber_refine_topk.1"),
              (2.5, 3.5, "jit_f/fusion.2"),
              (-1.0, 0.5, "jit_g/copy.1"),          # clipped to 0..0.5
              (9.5, 11.0, "jit_f/climber_refine_topk.1")]}}
    s = trace.reduce_events(ev)
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(0.5 + 1.5 + 0.5)
    assert s.idle_share == pytest.approx(1 - 2.5 / 10)
    assert s.family_s["climber_refine_topk"] == pytest.approx(1.5)
    assert s.family_s["fusion"] == pytest.approx(1.0)
    assert s.top_ops[0] == ("jit_f/climber_refine_topk.1",
                            pytest.approx(1.5))
    gaps = dict(s.idle_gaps)
    # 0.5..2.0 (mid 1.25: bench.tick), 3.5..9.5 (mid 6.5: no tick)
    assert gaps["bench.tick"] == pytest.approx(1.5)
    assert gaps[trace.NO_SPAN] == pytest.approx(6.0)
    assert s.breakdown()["device_ops"][0][0] == \
        "jit_f/climber_refine_topk.1"


def test_gap_inside_the_mesh_call_lands_on_its_part():
    """The program's spans inside ``fleet.mesh.query`` take the gaps that
    open while they do: here the fetch, after the device's work."""
    ev = {"host": [("bench.window", 0.0, 10.0),
                   ("serve.tick", 1.0, 6.0),
                   ("fleet.query", 1.1, 5.9),
                   ("fleet.route", 1.2, 1.5),
                   ("fleet.mesh.query", 1.6, 5.8),
                   ("fleet.mesh.put", 1.7, 2.0),
                   ("fleet.mesh.wait", 2.0, 4.0),
                   ("fleet.mesh.fetch", 4.0, 5.6)],
          "devices": {"/device:TPU:0": [(0.0, 1.2, "jit_f/fusion.1"),
                                        (1.5, 4.0, "jit_f/fusion.2"),
                                        (5.7, 10.0, "jit_f/fusion.3")]}}
    gaps = dict(trace.reduce_events(ev).idle_gaps)
    # 1.2..1.5 (mid 1.35: the route), 4.0..5.7 (mid 4.85: the fetch)
    assert gaps == {"fleet.route": pytest.approx(0.3),
                    "fleet.mesh.fetch": pytest.approx(1.7)}
    assert "fleet.mesh.query" not in gaps


@pytest.mark.parametrize("raw,name,family", [
    ("%climber_refine_topk.1 = (f32[8,1,512]) custom-call(s32[8,124] %a)",
     "climber_refine_topk.1", "climber_refine_topk"),
    ("%fusion.52 = s32[4000]{0:T(1024)S(1)} fusion(s32[8,1000] %pad.8)",
     "fusion.52", "fusion"),
    ("%copy-start.5 = (s32[1,517]) copy-start(s32[1,517] %x)",
     "copy-start.5", "copy-start"),
    ("%slice = f32[2] slice(f32[4] %a)", "slice", "slice")])
def test_op_names(raw, name, family):
    assert trace.op_name(raw) == name
    assert trace.op_family(name) == family


def test_no_window_or_no_device_gives_nothing():
    assert trace.reduce_events({"host": [], "devices": {}}) is None
    assert trace.reduce_events(
        {"host": [("bench.window", 0, 1)], "devices": {}}) is None


def test_recorded_chip_trace():
    """Three ticks of a 100,000-series rw256 fleet at K=500, traced on a
    TPU v5e: the reduction finds the window, the device ops, the refine
    kernel by name and the host spans on the same clock."""
    ev = trace.load_events(TESTDATA / "rw256-3ticks.xplane.pb.gz")
    assert list(ev["devices"]) == ["/device:TPU:0"]
    assert len(ev["devices"]["/device:TPU:0"]) == 3528
    names = [n for n, _, _ in ev["host"]]
    assert names.count("bench.tick") == 3 and names.count("bench.window") == 1
    s = trace.reduce_events(ev)
    assert s.chips == 1 and s.ops == 3499
    assert s.window_s == pytest.approx(0.121102897)
    assert s.busy_s == pytest.approx(0.0235057, rel=1e-4)
    assert 0.0 < s.busy_s < s.window_s
    kernel = s.family_s["climber_refine_topk"]
    assert kernel == pytest.approx(0.0214698, rel=1e-4)
    assert s.top_ops[0][0] == "jit_local_fn/climber_refine_topk.1"
    # every idle second is attributed, and the parts add up
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert dict(s.idle_gaps)[trace.NO_SPAN] == pytest.approx(0.0769788,
                                                             rel=1e-4)


def test_innermost_span_matches_a_full_scan():
    """The indexed look-up of the innermost open host span against a scan
    of every span, on nested and overlapping spans."""
    import random
    rnd = random.Random(7)
    spans = []
    for i in range(200):
        s = i * 1.0 + rnd.random() * 0.5
        spans.append((s, s + 0.9, "bench.tick"))
        spans.append((s + 0.1, s + 0.8, "bench.fleet_query"))
        spans.append((s + 0.2, s + 0.4, "bench.route"))
        if i % 7 == 0:                      # one outlasting its neighbours
            spans.append((s + 0.3, s + 3.5, "fleet.mesh.dispatch"))
    index = trace._Spans(spans)

    def scan(t):
        best = None
        for s, e, name in spans:
            if s <= t < e and (best is None or s > best[0]):
                best = (s, name)
        return best[1] if best else trace.NO_SPAN

    for _ in range(5000):
        t = rnd.uniform(-1.0, 205.0)
        assert index.innermost(t) == scan(t), t
