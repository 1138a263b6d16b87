"""Reduce a profiler trace of the window to the numbers the readers need.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``:

  * the window: the host span :data:`WINDOW_SPAN` that the harness holds
    open for exactly the measured seconds;
  * busy time: the union of the intervals in which an operation ran on a
    device (the ``XLA Ops`` line of each TPU plane), clipped to the window
    and averaged over the chips;
  * device time per operation family: an op event on a TPU is named by its
    HLO instruction (``%climber_refine_topk.1 = ... custom-call(...)``),
    and its family is that name without the ``%`` and the numeric suffix.
    A Pallas kernel's family is the name the program gives the kernel
    (``climber_refine_topk``).  The TPU trace carries no op metadata, so
    the program's ``jax.named_scope``s do not show there;
  * the device operations that took most time, and the device's idle gaps
    attributed to what the host was doing: the innermost of the benchmark's
    and the program's host spans (:data:`HOST_SPANS`) open at each gap's
    middle, or none.
"""
from __future__ import annotations

from collections import defaultdict
from bisect import bisect_right
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.tick", "bench.fleet_query", "bench.route",
              "fleet.mesh.query", "fleet.mesh.dispatch")
NO_SPAN = "no tick in flight"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                          # mean over the chips traced
    chips: int
    family_s: Dict[str, float]             # device seconds per op family
    top_ops: List[Tuple[str, float]]       # (op, device seconds), by time
    idle_gaps: List[Tuple[str, float]]     # (host span, idle seconds)
    ops: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def find_xplane(logdir) -> Optional[Path]:
    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def union_length(intervals: Sequence[Tuple[float, float]]):
    """``(total covered length, merged intervals)`` of ``[start, end)``
    intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_name(event_name: str) -> str:
    """``%fusion.52 = s32[4000] fusion(...)`` -> ``fusion.52``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_local_fn(13236863924411445534)`` -> ``jit_local_fn``."""
    return event_name.split("(", 1)[0]


def op_family(name: str) -> str:
    """``climber_refine_topk.1`` -> ``climber_refine_topk``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def load_events(path) -> dict:
    """Host spans and device ops of one trace, times in seconds.

    ``path`` is an ``.xplane.pb``, or one compressed with gzip
    (``.xplane.pb.gz``).  Returns ``{"host": [(name, start, end)],
    "devices": {plane: [(start, end, "module/op")]}}``, where module is
    the XLA program the op ran in (without its hash).
    """
    from jax.profiler import ProfileData
    path = str(path)
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    item = (s, s + e.duration_ns * 1e-9)
                    if line.name == OPS_LINE:
                        ops.append(item + (op_name(e.name),))
                    else:
                        mods.append(item + (module_name(e.name),))
            if ops:
                devices[plane.name] = _tag_modules(ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        s = e.start_ns * 1e-9
                        host.append((e.name, s, s + e.duration_ns * 1e-9))
    return {"host": host, "devices": devices}


def _tag_modules(ops, mods):
    """Prefix each op with the module whose run contains its start."""
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    out = []
    for s, e, name in ops:
        i = bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append((s, e, f"{mod}/{name}"))
    return out


def reduce_events(ev: dict, *, host_spans: Sequence[str] = HOST_SPANS
                  ) -> Optional[TraceSummary]:
    """The summary of :func:`load_events`' output, or None where the trace
    has no window span or no device operation."""
    windows = [(s, e) for name, s, e in ev["host"] if name == WINDOW_SPAN]
    if not windows or not ev["devices"]:
        return None
    w0, w1 = max(windows, key=lambda x: x[1] - x[0])
    family_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    spans = _Spans([(s, e, name) for name, s, e in ev["host"]
                    if name in host_spans])
    n_ops = 0
    for ops in ev["devices"].values():
        clipped = []
        for s, e, name in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            n_ops += 1
            clipped.append((s, e))
            family_s[op_family(name.rsplit("/", 1)[-1])] += e - s
            op_s[name] += e - s
        busy, merged = union_length(clipped)
        busy_total += busy
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[spans.innermost((a + b) / 2)] += b - a
    chips = len(ev["devices"])
    return TraceSummary(
        window_s=w1 - w0, busy_s=busy_total / chips, chips=chips,
        family_s={f: v / chips for f, v in family_s.items()},
        top_ops=sorted(((n, v / chips) for n, v in op_s.items()),
                       key=lambda kv: -kv[1]),
        idle_gaps=sorted(((n, s / chips) for n, s in gaps.items()),
                         key=lambda kv: -kv[1]),
        ops=n_ops)


class _Spans:
    """Host spans ``(start, end, name)``, asked which is the innermost
    open at a time: the one that started last of those open.  A window
    holds hundreds of thousands of idle gaps and thousands of spans, so a
    gap looks back from the last span to start before it and stops where
    no earlier span ends after it."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.reach, far = [], float("-inf")     # latest end so far
        for _, e, _ in self.spans:
            far = max(far, e)
            self.reach.append(far)

    def innermost(self, t: float) -> str:
        j = bisect_right(self.starts, t) - 1
        while j >= 0 and self.reach[j] > t:
            if self.spans[j][1] > t:
                return self.spans[j][2]
            j -= 1
        return NO_SPAN


def summarize(logdir, *, host_spans: Sequence[str] = HOST_SPANS
              ) -> Optional[TraceSummary]:
    path = find_xplane(logdir)
    if path is None:
        return None
    return reduce_events(load_events(path), host_spans=host_spans)
