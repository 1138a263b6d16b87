#!/usr/bin/env python3
"""One run of one benchmark cell on the served CLIMBER kNN path.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 bench/run.py --workload <name> --rehearse

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix.  A run makes the configuration's corpus on the device from
the configuration's fixed key and the queries from ``--seed``, builds the
fleet the configuration describes, serves it with
``FleetEngine`` behind ``ClimberServer`` on a one-device mesh, warms up
every batch shape the traffic can form, then lets a separate load
generator process drive it over localhost for ``--seconds``.  After the
window it reads the device's peak memory, asks the same server for an
exhaustive sample, frees the program, and checks every answer against a
brute-force reference (``bench/benchlib/check.py``).

With ``--trace 0`` the last line of standard output is the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the line carries the per-layer metrics, the device's busy and window
seconds and a breakdown.  Each metric is read by ``bench/metrics/<name>.py``.
The checks, each beside its limit, are the last lines of standard error
and the last key of the line.

No TPU, or fewer chips than the cell asks for: exit 2, no line.
``--rehearse`` runs the same path at a tiny size on the CPU (refine kernel
interpreted) and never prints the metrics line.
"""
import time

T_START = time.monotonic()

import argparse     # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402
from pathlib import Path    # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from benchlib import spec, traffic as traffic_mod  # noqa: E402
from benchlib.loadgen import LoadGenerator, OK  # noqa: E402

EXIT_NO_CHIP = 2
LOWER_SPAN = "jaxpr_to_mlir_module"       # one per program lowered


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's "
                         "run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's rehearsal "
                         "size; never prints a metrics line")
    return ap.parse_args(argv)


class CompileCounter:
    """Times of every program JAX lowers in this process."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if LOWER_SPAN in name:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run(args) -> dict:
    bm = spec.load_benchmark()
    spec.validate(bm)
    cell = spec.cell(bm, args.workload)
    cfg = spec.load_config(bm, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    traffic_mod.check_traffic(traffic)
    seconds = float(args.seconds or bm["run_seconds"])
    if args.rehearse:
        cfg, traffic = spec.rehearsal(cfg, traffic)
    gen = LoadGenerator()           # its imports overlap the set-up below
    try:
        return _run(args, bm, cell, cfg, traffic, seconds, gen)
    finally:
        gen.close()


def _run(args, bm, cell, cfg, traffic, seconds, gen) -> dict:
    import jax
    chips = int(cell["chips"])
    dev = device_info(jax, chips)
    if not args.rehearse and dev["platform"] != "tpu":
        log(f"JAX found no TPU (platform {dev['platform']!r}); exiting")
        return {"exit": EXIT_NO_CHIP}
    if len(jax.devices()) < chips:
        log(f"the cell needs {chips} chips, JAX sees {len(jax.devices())}")
        return {"exit": EXIT_NO_CHIP}
    from repro.utils.compile_cache import enable_compile_cache
    from repro.serve.net import serve_in_thread
    from benchlib import check, data, serve, trace as trace_mod, work
    from benchlib.record import RunRecord

    cache = enable_compile_cache()
    # cache every program, however fast it compiled, so that a warm run's
    # set-up loads rather than compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    log(f"device: {dev['platform']} {dev['kind']} x{dev['count']}"
        f"{' (REHEARSAL on CPU: no device metric)' if args.rehearse else ''};"
        f" compile cache {cache}")
    seed = args.seed
    n = int(cfg["climber"]["series_len"])
    bs = int(cfg["serving"]["batch_size"])
    k = int(cfg["serving"]["k"])

    t = time.monotonic()
    shards = data.make_corpus(cfg["corpus"], n, int(cfg["num_series"]))
    corpus = shards[0] if len(shards) == 1 else np.concatenate(shards)
    warm_q, window_q = data.run_queries(cfg, traffic, corpus, seconds, seed)
    log(f"data: {len(corpus):,} series of length {n} in {len(shards)} "
        f"shard(s), {len(window_q):,} window queries, made in "
        f"{time.monotonic() - t:.1f} s")

    t = time.monotonic()
    engine = serve.build(cfg, shards)
    del shards
    log(f"build: {engine.fleet.total_records:,} series, partitions "
        f"{[sh.index.store.num_partitions for sh in engine.fleet.shards]},"
        f" in {time.monotonic() - t:.1f} s")
    readers = {m["name"]: spec.load_reader(m["name"])
               for m in (spec.per_layer_for(bm, cell["name"]) if args.trace
                         else spec.end_to_end_for(bm, cell["name"]))}
    recorder = serve.TickRecorder(engine, keep_queries=bool(args.trace),
                                  annotate=bool(args.trace))
    used, secs = serve.warm_up(engine, warm_q)
    log(f"warm-up: ticks of 1..{bs} live rows in {sum(secs):.1f} s "
        f"(first, with the mesh layout, {secs[0]:.1f} s; then "
        f"{', '.join(f'{s:.1f}' for s in secs[1:])})")
    server, stop = serve_in_thread(engine)
    job = {"port": server.port, "k": k, "seconds": seconds,
           "queries": window_q, "warm": warm_q[used:],
           "mode": traffic["mode"],
           "connections": int(traffic["connections"]),
           "outstanding": int(traffic.get("outstanding", 0)),
           "offsets": (traffic_mod.open_schedule(
               float(traffic["rate_qps"]), seconds, seed)
               if traffic["mode"] == "open" else None)}
    gen.prepare(job)
    hits0 = engine.stats.plan_cache_hits

    tracedir = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if args.trace else None
    if tracedir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tracedir.name, profiler_options=opts)
    t0 = time.monotonic() + 0.05
    gen.start(t0)
    setup_s = t0 - T_START
    t_end = t0 + seconds
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        time.sleep(max(t_end - time.monotonic(), 0.0))
    if tracedir is not None:
        jax.profiler.stop_trace()
    res = gen.result(timeout=seconds + 120.0)
    in_window = compiles.between(t0, t_end)
    mem_peak = peak_memory(jax, chips)
    hits = engine.stats.plan_cache_hits - hits0
    ok = res["status"] == OK
    attempted, failed = len(ok), int((~ok).sum())
    issued_q = window_q[:attempted]
    lat_ms = (res["done"][ok] - res["due"][ok]) * 1e3
    late = traffic_mod.lateness_summary(res["sent"], res["due"])
    log(f"window: {attempted:,} requests, {int(ok.sum()):,} answered, "
        f"{failed} failed, {int(res['refusals'].sum())} RETRY_LATER "
        f"refusals retried")
    log(f"generator lateness: p50 {late['p50_ms']:.3f} ms, p99 "
        f"{late['p99_ms']:.3f} ms, max {late['max_ms']:.3f} ms")
    if lat_ms.size:
        from benchlib.stats import percentile
        log(f"latency from due (recorded, not judged): p50 {percentile(lat_ms, 50):.3f} ms, p95 "
            f"{percentile(lat_ms, 95):.3f} ms, p99 "
            f"{percentile(lat_ms, 99):.3f} ms")
    log(f"compilations inside the window: {in_window}")
    log(f"plan-cache hits inside the window: {hits}")
    log(f"peak device memory: {mem_peak:,} bytes")

    pick = data.exhaustive_pick(seed, np.flatnonzero(ok), bs)
    t = time.monotonic()
    exh_d, exh_g = serve.exhaustive_answers(server.port, engine,
                                            issued_q[pick])
    log(f"exhaustive sample of {len(pick)} through the same server in "
        f"{time.monotonic() - t:.1f} s")

    win = recorder.window(t0, t_end)
    record = RunRecord(
        seconds=seconds, setup_s=setup_s, due=res["due"], done=res["done"],
        ok=ok, window_end=t_end, batch_size=bs,
        tick_secs=np.asarray(recorder.secs)[win],
        tick_live=np.asarray(recorder.live)[win],
        device_kind=dev["kind"])
    out_metrics, breakdown, device = {}, None, dict(dev)
    device["memory_peak_bytes"] = mem_peak
    if args.trace:
        t = time.monotonic()
        record.trace = trace_mod.summarize(tracedir.name)
        tracedir.cleanup()
        log(f"trace reduced in {time.monotonic() - t:.1f} s")
        ticks = [(recorder.queries[i], recorder.masks[i]) for i in win]
        fleet, variant = engine.fleet, engine.variant
        record.refine_bytes = lambda: work.refine_bytes(fleet, ticks,
                                                        variant)
        t = time.monotonic()
        out_metrics = _read(readers, record, bm)
        log(f"per-layer metrics read in {time.monotonic() - t:.1f} s")
        if record.trace is not None:
            device["busy_s"] = record.trace.busy_s
            device["window_s"] = record.trace.window_s
            breakdown = record.trace.breakdown()
        record.refine_bytes = None
        del fleet

    # free the program before the reference runs on the device
    stop()
    del engine, recorder, server
    gc.collect()
    t = time.monotonic()
    verdict = check.judge(
        corpus, queries=issued_q[ok], dist=res["dist"][ok],
        gid=res["gid"][ok], unanswered=failed, exh_queries=issued_q[pick],
        exh_dist=exh_d, exh_gid=exh_g, limits=cfg["limits"])
    log(f"reference over {int(ok.sum()) + len(pick):,} queries and "
        f"{len(corpus):,} series in {time.monotonic() - t:.1f} s; rms of "
        f"the distance gaps {verdict['dist_rms']:.3e}")
    record.recall = verdict["recall"]
    if not args.trace:
        out_metrics = _read(readers, record, bm)
    line = {"correct": verdict["correct"], "attempted": attempted,
            "failed": failed, "metrics": out_metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = verdict["checks"]
    return {"exit": 0, "line": line}


def _read(readers: dict, record, bm: dict) -> dict:
    units = {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]}
    out = {}
    for name, read in readers.items():
        value = read(record)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    out = run(args)
    if out["exit"]:
        return out["exit"]
    line = out["line"]
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    if args.rehearse:
        log(f"rehearsal: correct={line['correct']} attempted="
            f"{line['attempted']} failed={line['failed']}; metrics read: "
            f"{sorted(line['metrics'])} (CPU: no device metric)")
        return 0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
