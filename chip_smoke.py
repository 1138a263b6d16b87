#!/usr/bin/env python3
"""Smoke test of the served CLIMBER kNN path on TPU — not a benchmark.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --chips 4      # four chips (one host, 2x2)
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse 3000 [--chips 4]

One chip: an ``IndexFleet`` of 4 tenant shards x 500,000 random-walk
series of length 256 (2,000,000 in all, generated from ``--seed``) at the
paper's ``ClimberConfig()`` defaults (w=16, r=200, m=10, capacity 3000,
K=500, Adaptive-4X), laid out on a 1-device mesh, served through
``FleetEngine`` -> ``ClimberServer`` -> ``ClimberClient`` over localhost:

  * batches of 8 at k=500 and k=10 with adaptive routing; routed recall@k
    against a float64 NumPy brute-force scan of the host copy of the data;
  * the exhaustive route (``routing="exhaustive", variant="exhaustive"``)
    at k=10 and k=500 must agree with that scan: tie-tolerant recall 1.0
    and squared distances within ``n`` float32 ulps of ‖q‖²+‖x‖² (the
    worst-case rounding of a length-n float32 dot);
  * 10,000 fresh series are inserted, 8 of them self-queried through the
    delta, and one ``compact()`` seals them; its ticket must carry no error
    and the sealed shard must still answer the self-queries;
  * the refine kernel must lower to a Mosaic ``tpu_custom_call``.

``--chips 4`` runs only the four-chip fleet: one 2,000,000-series tenant
shard built and kept on each chip, the mesh placement compared with the
host-loop placement (identical gids, distances within the stated float32
tolerance) and with the float64 scan, and what each chip holds printed.

Times printed are one run in this process (compilation included where
labelled; the first line says whether the compile cache was warm).  Any
failed check, an error reply, or a backend other than TPU
exits non-zero; the last line of a passing run is the JSON contract
``{"ok": true, "device": {...}}``.  ``--rehearse N`` (N series per shard) is
for a CPU rehearsal at a tiny size: the kernel runs in interpret mode and
the run ends with a rehearsal line, never the contract line.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED_QUERIES = 8
FRESH = 10_000


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, msg: str) -> None:
    """Raise (never skipped, unlike ``assert`` under ``-O``)."""
    if not ok:
        raise SmokeFailure(msg)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", type=int, default=0, metavar="N",
                    help="CPU rehearsal with N series per shard (never a "
                         "chip result)")
    args = ap.parse_args()

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev0.platform!r})",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({cached} entries at start: "
        f"{'warm' if cached else 'cold'})")
    log(f"device: {dev0.platform} {dev0.device_kind} x{len(jax.devices())}"
        f"{' (REHEARSAL on CPU: not a chip run)' if args.rehearse else ''}")
    log("smoke test of the served path, not a benchmark: every time is "
        "one run, compilation included where labelled")
    if args.chips == 4:
        run_four_chips(args)
    else:
        run_one_chip(args)
    if args.rehearse:
        log("rehearsal passed (CPU; no device result)")
        return 0
    devs = jax.devices()[:args.chips]
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


# ---------------------------------------------------------------------------
# reference and checks
# ---------------------------------------------------------------------------
def exact_knn(data: np.ndarray, queries: np.ndarray, k: int):
    """Plain float64 brute force: the ``2k`` best by a float64 expansion,
    re-ranked on directly computed ``sum((x - q)**2)``; ties by row id."""
    q = queries.astype(np.float64)
    d2 = np.empty((len(q), len(data)), np.float64)
    step = 1 << 18
    for i in range(0, len(data), step):
        x = data[i:i + step].astype(np.float64)
        d2[:, i:i + step] = ((x * x).sum(1)[None, :] - 2.0 * (q @ x.T)
                             + (q * q).sum(1)[:, None])
    cand = np.argpartition(d2, 2 * k, axis=1)[:, :2 * k]
    dist, gid = np.empty((len(q), k)), np.empty((len(q), k), np.int64)
    for r in range(len(q)):
        c = np.sort(cand[r])
        dd = ((data[c].astype(np.float64) - q[r]) ** 2).sum(1)
        order = np.lexsort((c, dd))[:k]
        dist[r], gid[r] = np.sqrt(dd[order]), c[order]
    return dist, gid


class Reference:
    """Float64 top-``kmax`` over the fleet's contents (gids = row ids)."""

    def __init__(self, data, queries, kmax, n):
        t = time.perf_counter()
        self.dist, self.gid = exact_knn(data, queries, kmax)
        # squared distances of z-normalised series are bounded by 2n; a
        # length-n float32 dot rounds by at most n ulps of that bound
        self.scale, self.ulps = 2 * n, n
        self.tol_sq = self.ulps * float(np.finfo(np.float32).eps) * self.scale
        log(f"float64 reference: {len(queries)} queries x {len(data):,} "
            f"series, top-{kmax}, {time.perf_counter() - t:.1f} s (host)")

    def recall(self, dist, gid, k) -> float:
        from repro.eval.metrics import recall_at_k
        # the distance slack at the k-th neighbour that tol_sq implies
        b = float(self.dist[:, k - 1].min())
        tie = float(np.sqrt(b * b + self.tol_sq) - b)
        return recall_at_k(gid[:, :k], self.gid[:, :k], k,
                           approx_dist=dist[:, :k],
                           exact_dist=self.dist[:, :k], tie_tol=tie)

    def check_exact(self, dist, gid, k, what: str) -> None:
        from repro.eval.metrics import distance_error
        rec = self.recall(dist, gid, k)
        err, tol_sq = distance_error(dist[:, :k], self.dist[:, :k],
                                     scale=self.scale, ulps=self.ulps)
        same = float(np.mean(gid[:, :k] == self.gid[:, :k]))
        log(f"{what} k={k}: recall@{k} {rec:.4f} vs float64 scan, rank-wise "
            f"max |d^2 - d64^2| {err:.3e} (limit {tol_sq:.3e}), "
            f"identical gid slots {same:.4f}")
        if rec != 1.0 or err > tol_sq:
            raise SmokeFailure(f"{what} k={k} disagrees with the float64 "
                               f"reference")


def check_kernel_lowers(jax, store, q, mp, k) -> None:
    """The refine program must be the Mosaic kernel, not the interpreter
    or the dense oracle."""
    import jax.numpy as jnp
    from repro.core.refine import resolve_use_kernel
    from repro.kernels import ops as kernel_ops
    check(resolve_use_kernel(None), "backend default is not the kernel")
    check(not kernel_ops._interpret(), "kernel would run interpreted")
    plan = jnp.zeros((q, mp), jnp.int32)
    text = jax.jit(kernel_ops.fused_refine_topk_device_plan,
                   static_argnames="k").lower(
        store.data, store.norms, store.rec_dfs, store.rec_gid,
        jnp.zeros((q, store.data.shape[-1]), jnp.float32), plan, plan,
        plan, k=k).compile().as_text()
    check("tpu_custom_call" in text, "refine program has no Mosaic kernel")
    log(f"refine program at the served shapes (Q={q}, MP={mp}, k={k}) "
        f"contains tpu_custom_call")


def device_report(jax, label: str) -> None:
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if st:
            log(f"{label}: {d} bytes_in_use {st.get('bytes_in_use', 0):,} "
                f"peak_bytes_in_use {st.get('peak_bytes_in_use', 0):,}")


def served(client, queries, k, *, retries: int = 50):
    """One pipelined batch over the socket; RETRY_LATER is retried after
    its hint, any other refusal raises."""
    from repro.serve.net import RetryLater
    for _ in range(retries):
        try:
            res = client.query_batch(list(queries), k=k)
            return (np.stack([r.dist for r in res]),
                    np.stack([r.gid for r in res]))
        except RetryLater as exc:
            time.sleep(max(exc.retry_after_ms, 10.0) / 1e3)
    raise RuntimeError(f"RETRY_LATER never answered after {retries} tries")


def make_shards(jax, key, count, per, n):
    """``count`` random-walk shards, generated one at a time on the default
    device and kept on the host."""
    from repro.data import make_dataset
    return [np.asarray(make_dataset("randomwalk", jax.random.fold_in(key, s),
                                    per, n)) for s in range(count)]


# ---------------------------------------------------------------------------
# one chip: the served path end to end
# ---------------------------------------------------------------------------
def run_one_chip(args) -> None:
    import jax
    from repro.eval.metrics import answers_agree
    from repro.fleet import FleetConfig, FleetEngine, IndexFleet
    from repro.launch.mesh import make_mesh
    from repro.serve import api
    from repro.serve.net import ClimberClient, serve_in_thread
    from repro.utils.config import ClimberConfig

    cfg = ClimberConfig()                    # the paper's defaults
    n, kmax = cfg.series_len, cfg.k
    shards_n, per = 4, args.rehearse or 500_000
    key = jax.random.PRNGKey(args.seed)
    if args.rehearse:
        log(f"cut: {per:,} series per shard (rehearsal size)")

    t = time.perf_counter()
    data = np.concatenate(make_shards(jax, key, shards_n, per, n))
    log(f"data: {len(data):,} random-walk series of length {n} "
        f"({data.nbytes:,} bytes f32), generated in "
        f"{time.perf_counter() - t:.1f} s")

    t = time.perf_counter()
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, auto_compact=False,
                                   seed=args.seed))
    for s in range(shards_n):
        fleet.add_shard(f"tenant{s}", data[s * per:(s + 1) * per])
    build_s = time.perf_counter() - t
    store_bytes = sum(sum(x.nbytes for x in sh.index.store)
                      for sh in fleet.shards)
    layout = ", ".join(f"{sh.key}: P={sh.index.store.num_partitions} "
                       f"cap={sh.index.store.capacity}"
                       for sh in fleet.shards)
    log(f"build: {fleet.total_records:,} series in {len(fleet.shards)} "
        f"shards in {build_s:.1f} s; store {store_bytes:,} bytes ({layout})")
    fleet.attach_mesh(make_mesh((1,), ("data",)))
    device_report(jax, "after build")

    rng = np.random.default_rng(args.seed)
    queries = data[rng.choice(len(data), SEED_QUERIES, replace=False)]
    ref = Reference(data, queries, kmax, n)

    # served path: adaptive routing, one engine at k_max = K, k=10 and
    # k=500 requests (a k=10 request is the first 10 of the K answer)
    engine = FleetEngine(fleet, config=api.ServingConfig(
        batch_size=SEED_QUERIES, k=kmax, routing="adaptive",
        variant="adaptive", use_kernel=True, flush_interval_ms=100.0))
    server, stop = serve_in_thread(engine)
    try:
        with ClimberClient("127.0.0.1", server.port, timeout=1200) as cl:
            t = time.perf_counter()
            d500, g500 = served(cl, queries, kmax)
            first_s = time.perf_counter() - t
            t = time.perf_counter()
            d10, g10 = served(cl, queries, 10)
            d500b, g500b = served(cl, queries, kmax)
            steady_s = (time.perf_counter() - t) / 2
            log(f"served: first batch of {SEED_QUERIES} at k={kmax} "
                f"{first_s:.2f} s (placement layout + compile + answer); "
                f"then {steady_s:.3f} s per batch")
            check(np.array_equal(g10, g500[:, :10]), "k=10 != K prefix")
            ok, why = answers_agree(d500, g500, d500b, g500b, scale=2 * n)
            check(ok, f"repeat batch differs: {why}")
            dd, gd, _ = fleet.query(queries, kmax, routing="adaptive",
                                    variant="adaptive", use_kernel=True)
            ok, why = answers_agree(d500, g500, dd, gd, scale=2 * n)
            check(ok, f"socket answer != direct fleet.query: {why}")
            for k in (10, kmax):
                log(f"routed (adaptive) recall@{k}: "
                    f"{ref.recall(d500, g500, k):.4f} vs float64 scan")

            for k in (10, kmax):
                t = time.perf_counter()
                de, ge, info = fleet.query(queries, k, routing="exhaustive",
                                           variant="exhaustive",
                                           use_kernel=True)
                log(f"exhaustive route k={k}: {time.perf_counter() - t:.2f}"
                    f" s incl. compile, {int(info.candidates_scanned[0]):,}"
                    f" candidates per query")
                ref.check_exact(de, ge, k, "exhaustive route")
            device_report(jax, "after queries")
            if args.rehearse:
                log("kernel lowering check skipped (CPU rehearsal)")
            else:
                check_kernel_lowers(jax, fleet.shards[0].index.store,
                                    SEED_QUERIES, 16, kmax)

            # ingest -> self-query through the delta -> seal
            fresh = make_shards(jax, jax.random.fold_in(key, 1000), 1,
                                FRESH, n)[0]
            t = time.perf_counter()
            gids = fleet.insert(fresh)
            log(f"insert: {len(gids):,} series in "
                f"{time.perf_counter() - t:.1f} s (delta occupancy "
                f"{fleet.delta.occupancy:,})")
            probe, want = fresh[:SEED_QUERIES], gids[:SEED_QUERIES]
            tol_d = float(np.sqrt(ref.tol_sq))     # a self-match's distance

            def self_query(when: str) -> None:
                # served (adaptive, approximate): reported; exhaustive
                # (exact): each probe must find itself at distance ~0
                t = time.perf_counter()
                _, gs = served(cl, probe, 10)
                hit = float(np.mean(gs[:, 0] == want))
                dx, gx, _ = fleet.query(probe, 10, routing="exhaustive",
                                        variant="exhaustive",
                                        use_kernel=True)
                log(f"self-query {when}: served adaptive top-1 hit rate "
                    f"{hit:.3f}; exhaustive top-1 gids {gx[:, 0].tolist()}"
                    f" (inserted {want.tolist()}), max d "
                    f"{float(dx[:, 0].max()):.2e} (limit {tol_d:.2e}); "
                    f"{time.perf_counter() - t:.2f} s incl. compile")
                check(np.array_equal(gx[:, 0], want)
                      and float(dx[:, 0].max()) <= tol_d,
                      f"self-query {when} missed")

            self_query("through the delta")
            t = time.perf_counter()
            ticket = fleet.compact_async()
            handle = ticket.wait()
            check(ticket.error is None, f"compaction error {ticket.error}")
            log(f"compact(): sealed {handle.key} ({handle.num_records:,} "
                f"series) in {time.perf_counter() - t:.1f} s, ticket error "
                f"{ticket.error}; {len(fleet.shards)} sealed shards")
            self_query("after the seal")
    finally:
        stop()
    device_report(jax, "end")


# ---------------------------------------------------------------------------
# four chips: one tenant shard per chip, mesh vs host loop vs float64
# ---------------------------------------------------------------------------
def run_four_chips(args) -> None:
    import jax
    from repro.eval.metrics import answers_agree
    from repro.fleet import FleetConfig, IndexFleet
    from repro.launch.mesh import make_mesh
    from repro.utils.config import ClimberConfig

    cfg = ClimberConfig()
    n, kmax = cfg.series_len, cfg.k
    devices = jax.devices()[:4]
    per = args.rehearse or 2_000_000
    key = jax.random.PRNGKey(args.seed)
    if args.rehearse:
        log(f"cut: {per:,} series per shard (rehearsal size)")

    t = time.perf_counter()
    data = np.concatenate(make_shards(jax, key, 4, per, n))
    log(f"data: {len(data):,} series of length {n}, one tenant shard of "
        f"{per:,} per chip, generated in {time.perf_counter() - t:.1f} s")
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, auto_compact=False,
                                   seed=args.seed))
    for s, dev in enumerate(devices):
        t = time.perf_counter()
        fleet.add_shard(f"tenant{s}", data[s * per:(s + 1) * per],
                        device=dev)
        log(f"build: tenant{s} ({per:,} series) in "
            f"{time.perf_counter() - t:.1f} s, index kept on {dev}")
    device_report(jax, "after build")
    fleet.attach_mesh(make_mesh((4,), ("data",)))

    rng = np.random.default_rng(args.seed)
    queries = data[rng.choice(len(data), SEED_QUERIES, replace=False)]
    ref = Reference(data, queries, kmax, n)
    del data

    for routing in ("exhaustive", "adaptive"):
        out = {}
        for placement in ("mesh", "host"):
            t = time.perf_counter()
            out[placement] = fleet.query(queries, kmax, routing=routing,
                                         variant=routing, use_kernel=True,
                                         placement=placement)
            log(f"{routing} k={kmax} placement={placement}: "
                f"{time.perf_counter() - t:.2f} s incl. compile")
        (dm, gm, _), (dh, gh, _) = out["mesh"], out["host"]
        ok, why = answers_agree(dm, gm, dh, gh, scale=2 * n)
        log(f"{routing}: mesh vs host loop: {why}")
        check(ok, f"mesh placement != host loop ({routing}): {why}")
        if routing == "exhaustive":
            for k in (10, kmax):
                ref.check_exact(dm, gm, k, "mesh exhaustive route")
        else:
            for k in (10, kmax):
                log(f"routed (adaptive) recall@{k}: "
                    f"{ref.recall(dm, gm, k):.4f} vs float64 scan")

    # what each chip holds: its slab of the stacked store and its shard
    store = fleet._ensure_placement().store
    counts = {s.device: np.asarray(s.data) for s in
              store.count.addressable_shards}
    for sh in store.data.addressable_shards:
        slots = range(*sh.index[0].indices(store.data.shape[0]))
        live = int(counts[sh.device].sum())
        own = [fleet.shards[j].key for j in slots if j < len(fleet.shards)]
        homes = {fleet.shards[j].key: str(
            next(iter(fleet.shards[j].index.store.data.devices())))
            for j in slots if j < len(fleet.shards)}
        log(f"{sh.device}: mesh slots {list(slots)} = {own}, "
            f"{live:,} live series, slab {sh.data.nbytes:,} bytes; "
            f"shard index stores on {homes}")
        check(live == per, f"{sh.device} holds {live} series, not {per}")
    device_report(jax, "end")


if __name__ == "__main__":
    sys.exit(main())
