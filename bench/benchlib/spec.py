"""Find the cell, its configuration, its traffic and its metrics by name.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

  * ``BENCHMARK.json``'s ``configs[].file``          a configuration;
  * ``bench/workloads/<traffic>.json``             a traffic mix;
  * ``bench/metrics/<metric>.py``                  a metric reader with
    ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json, a configuration or a traffic file is malformed."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r} is not a valid name")
    return name


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def cell(bm: dict, workload: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == workload:
            return w
    raise SpecError(f"unknown workload {workload!r}; BENCHMARK.json has "
                    f"{[w['name'] for w in bm['workloads']]}")


def load_config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise SpecError(f"unknown configuration {name!r}")


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    check_name(name, "traffic")
    path = bench / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end_for(bm: dict, workload: str) -> List[dict]:
    return [m for m in bm["end_to_end"] if _applies(m, workload)]


def per_layer_for(bm: dict, workload: str) -> List[dict]:
    """Per-layer metrics of the cell: those that list it, and those without
    a ``workloads`` key whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bm, workload)}
    out = []
    for m in bm["per_layer"]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def load_reader(metric: str, bench: Path = BENCH):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    check_name(metric, "metric")
    path = bench / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {metric!r}")
    mod_name = "bench_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def validate(bm: dict, root: Path = ROOT) -> None:
    """The checks on names, units and cross references that a malformed
    entry would otherwise only fail mid-run."""
    names: Dict[str, str] = {}
    for c in bm["configs"]:
        check_name(c["name"], "configuration")
        for key in c["reduced"]:
            check_name(key, "reduced key")
        if not (root / c["file"]).is_file():
            raise SpecError(f"configuration file {c['file']} missing")
    configs = {c["name"] for c in bm["configs"]}
    pairs = set()
    for w in bm["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["traffic"], "traffic")
        if w["config"] not in configs:
            raise SpecError(f"{w['name']}: unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"{w['name']}: (config, traffic) appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            raise SpecError(f"{w['name']}: chips must be 1 or 4")
        load_traffic(w["traffic"], root / "bench")
    for kind in ("end_to_end", "per_layer"):
        for m in bm[kind]:
            check_name(m["name"], "metric")
            if m["name"] in names:
                raise SpecError(f"metric {m['name']!r} appears twice")
            names[m["name"]] = kind
            if not UNIT_RE.match(m["unit"]):
                raise SpecError(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"{m['name']}: better must be lower|higher")
            load_reader(m["name"], root / "bench")
    for m in bm["per_layer"]:
        if names.get(m["moves"]) != "end_to_end":
            raise SpecError(f"{m['name']}: moves {m['moves']!r} is not an "
                            "end-to-end metric")


def rehearsal(cfg: dict, traffic: dict) -> tuple:
    """The configuration and traffic cut to the rehearsal size."""
    r = cfg["rehearsal"]
    cfg = dict(cfg, num_series=r["num_series"],
               corpus=dict(cfg["corpus"], chunk=r["chunk"]),
               serving=dict(cfg["serving"], batch_size=r["batch_size"]))
    traffic = dict(traffic)
    if traffic["mode"] == "open":
        traffic["rate_qps"] = r["rate_qps"]
    else:
        traffic["outstanding"] = r["outstanding"]
        traffic["pool"] = r["pool"]
    return cfg, traffic
