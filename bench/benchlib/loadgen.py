"""The load generator: a separate process that drives the server over
localhost with ``AsyncClimberClient`` connections.

It runs in a process of its own (``spawn``) so that its clock and its
sends do not wait on the server's interpreter lock, as a remote client's
would not.  It never touches an accelerator: it pins JAX to the CPU before
the client library (which imports JAX) is loaded.

Protocol over one ``multiprocessing`` pipe: the parent sends the job, the
child answers ``"ready"`` once every connection has shaken hands and
warmed up, the parent sends the window's start on the shared monotonic
clock, and the child sends back one record per request and the answers.
"""
from __future__ import annotations

import os
import time

import numpy as np

OK, ERROR, TIMEOUT = 0, 1, 2
DRAIN_S = 60.0            # how long past the close an answer is waited for


def child_main(conn) -> None:
    """Entry point of the load-generator process."""
    os.environ["JAX_PLATFORMS"] = "cpu"     # the chip belongs to the server
    import asyncio
    import traceback
    job = conn.recv()
    try:
        conn.send(asyncio.run(_drive(job, conn)))
    except Exception:           # the parent reports it and exits non-zero
        conn.send({"error": traceback.format_exc()})
    finally:
        conn.close()


async def _drive(job: dict, conn) -> dict:
    import asyncio
    from repro.serve.net.client import (AsyncClimberClient, RetryLater,
                                        ServerError)

    k = int(job["k"])
    queries = job["queries"]
    clients = [await AsyncClimberClient.connect(
        "127.0.0.1", job["port"], client_name=f"bench-c{c}")
        for c in range(int(job["connections"]))]
    warm = job["warm"]
    await asyncio.gather(*(cl.query(warm[i % len(warm)], k)
                           for i, cl in enumerate(clients)))
    conn.send("ready")
    t0 = float(conn.recv())
    t_end = t0 + float(job["seconds"])
    deadline = t_end + DRAIN_S

    n_max = len(queries)
    due = np.full(n_max, np.nan)
    sent = np.full(n_max, np.nan)
    done = np.full(n_max, np.nan)
    status = np.full(n_max, -1, np.int8)
    refusals = np.zeros(n_max, np.int32)
    dist = np.zeros((n_max, k), np.float32)
    gid = np.full((n_max, k), -1, np.int32)

    async def one(cl, i: int, t_due: float) -> None:
        due[i] = t_due
        while True:
            now = time.monotonic()
            if np.isnan(sent[i]):
                sent[i] = now
            try:
                res = await asyncio.wait_for(cl.query(queries[i], k),
                                             timeout=max(deadline - now,
                                                         0.001))
            except RetryLater as exc:
                refusals[i] += 1
                await asyncio.sleep(max(exc.retry_after_ms, 1.0) / 1e3)
                continue
            except ServerError:
                status[i] = ERROR
                return
            except asyncio.TimeoutError:
                status[i] = TIMEOUT
                return
            done[i] = time.monotonic()
            dist[i], gid[i] = res.dist, res.gid
            status[i] = OK
            return

    await asyncio.sleep(max(t0 - time.monotonic(), 0.0))
    issued = 0
    if job["mode"] == "closed":
        async def worker(cl) -> None:
            nonlocal issued
            while time.monotonic() < t_end:
                if issued >= n_max:
                    raise RuntimeError(f"query pool of {n_max} exhausted")
                i = issued
                issued += 1
                await one(cl, i, time.monotonic())

        await asyncio.gather(*(worker(clients[w % len(clients)])
                               for w in range(int(job["outstanding"]))))
    else:
        tasks = []
        for i, off in enumerate(job["offsets"]):
            wait = t0 + off - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            tasks.append(asyncio.ensure_future(
                one(clients[i % len(clients)], i, t0 + off)))
            issued = i + 1
        await asyncio.gather(*tasks)
    for cl in clients:
        await cl.close()
    n = issued
    return {"due": due[:n], "sent": sent[:n], "done": done[:n],
            "status": status[:n], "refusals": refusals[:n],
            "dist": dist[:n], "gid": gid[:n]}


class LoadGenerator:
    """The parent's handle on the load-generator process."""

    def __init__(self):
        import multiprocessing as mp
        ctx = mp.get_context("spawn")
        self._conn, child = ctx.Pipe()
        self.proc = ctx.Process(target=child_main, args=(child,),
                                name="bench-loadgen", daemon=True)
        self.proc.start()
        child.close()
        self._finished = False

    def prepare(self, job: dict, timeout: float = 300.0) -> None:
        """Hand over the job; return once the connections are warm."""
        self._conn.send(job)
        if not self._conn.poll(timeout):
            raise RuntimeError("load generator did not get ready")
        msg = self._conn.recv()
        if msg != "ready":
            raise RuntimeError(f"load generator failed:\n{msg}")

    def start(self, t0: float) -> None:
        self._conn.send(t0)

    def result(self, timeout: float) -> dict:
        if not self._conn.poll(timeout):
            raise RuntimeError("load generator sent no result")
        res = self._conn.recv()
        self._finished = True
        if "error" in res:
            raise RuntimeError(f"load generator failed:\n{res['error']}")
        return res

    def close(self) -> None:
        """Wait for the process; one that never finished a job is ended."""
        self.proc.join(timeout=30 if self._finished else 0)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(timeout=10)
        self._conn.close()
