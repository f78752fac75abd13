#!/usr/bin/env python3
"""The load generator: a child process of its own, so it shares no
interpreter lock with the server it measures, and it never imports JAX
(the parent holds the chip).

One general generator reads the traffic mix's parameters:

- ``loop: "open"``: arrivals on a schedule at ``rate_rps``. The gaps are
  one fixed exponential draw (``schedule_seed``) that every ``--seed``
  replays in another order, so no seed gets a burstier minute than
  another. A request is timed from when it was *due*; how late it was
  really sent is reported beside it.
- ``loop: "closed"``: a work queue under ``in_flight`` slots, as the bulk
  client's semaphore: the next request is sent the moment one has answered.
- ``members: "uniform"`` draws each request's member from the seed;
  ``"round_robin"`` walks the bank.

Protocol with the parent, on this process's pipes: a JSON job on the first
line of stdin; ``ready`` on stdout after set-up (bodies made, an unmeasured
burst sent); ``go`` on stdin opens the window; then ``done <json>`` on stdout
and ``blob_bytes`` of ``.npz`` (latencies and the sampled responses).
"""

import asyncio
import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from harness import weights, wire  # noqa: E402

REQUEST_TIMEOUT_S = 60.0


def open_schedule(traffic: dict, rng: np.random.Generator, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop:
    exactly ``rate_rps * seconds`` arrivals whose gaps are one fixed
    exponential draw (``schedule_seed``), scaled to fill the window and
    replayed in the order ``rng`` (the run's seed) puts them. Every seed
    offers the same requests over the same time; only the order of the
    gaps, and so where the bursts fall, differs."""
    n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    gaps = np.random.default_rng(int(traffic["schedule_seed"])).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps[:-1][rng.permutation(n)])


class Generator:
    def __init__(self, job: dict):
        self.job = job
        self.traffic = job["traffic"]
        self.seed = job["seed"]
        self.rows = int(self.traffic["request_rows"])
        self.n_members = int(job["n_members"])
        pool = int(self.traffic.get("body_pool", 32))
        self.bodies = [
            wire.pack([("X", weights.request_body(job["config"], self.seed, i, self.rows))])
            for i in range(pool)
        ]
        self.rng = weights.rng_for(self.seed, weights.ARRIVALS)
        self.sample_rng = weights.rng_for(self.seed, weights.SAMPLE)
        self.sample_n = int(self.traffic["check_requests"])
        self.sent = 0
        self.records = []  # (latency_s, late_s, ok, t_done)
        self.samples = []  # (ordinal, member, body, raw)
        self.http = None

    def _pick(self, ordinal: int):
        if self.traffic["members"] == "round_robin":
            member = ordinal % self.n_members
        else:
            member = int(self.rng.integers(self.n_members))
        return member, ordinal % len(self.bodies)

    def _url(self, member: int) -> str:
        return f"{self.job['base_url']}/m-{member:05d}/anomaly/prediction"

    async def request(self, t_due: float, measured: bool) -> None:
        ordinal = self.sent
        self.sent += 1
        member, body = self._pick(ordinal)
        t_sent = time.monotonic()
        ok, raw = False, b""
        try:
            async with self.http.post(
                self._url(member), data=self.bodies[body],
                headers={"Content-Type": wire.CONTENT_TYPE},
            ) as resp:
                raw = await resp.read()
                if resp.status == 200:
                    frames = wire.unpack(raw)  # the whole response, decoded
                    ok = all(name in frames for name in wire.ANOMALY_FRAMES)
        except Exception as exc:  # a failed request is counted, never timed as a fast one
            print(f"request {ordinal} failed: {type(exc).__name__}: {exc}", file=sys.stderr)
        t_done = time.monotonic()
        if not measured:
            return
        self.records.append((t_done - t_due, t_sent - t_due, ok, t_done))
        if ok:
            # reservoir sample of the window's answers, drawn from the seed
            k = len(self.records)
            if len(self.samples) < self.sample_n:
                self.samples.append((ordinal, member, body, raw))
            else:
                j = int(self.sample_rng.integers(k))
                if j < self.sample_n:
                    self.samples[j] = (ordinal, member, body, raw)

    async def open_loop(self, seconds: float, measured: bool) -> None:
        due = open_schedule(self.traffic, self.rng, seconds)
        t0 = time.monotonic()
        tasks = []
        for t in due:
            delay = t0 + t - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(self.request(t0 + t, measured)))
        self.t_close = t0 + seconds
        await asyncio.sleep(max(0.0, self.t_close - time.monotonic()))
        if tasks:
            await asyncio.wait(tasks, timeout=REQUEST_TIMEOUT_S)

    async def closed_loop(self, seconds: float, measured: bool) -> None:
        t0 = time.monotonic()
        self.t_close = t0 + seconds

        async def client():
            while time.monotonic() < self.t_close:
                await self.request(time.monotonic(), measured)

        await asyncio.gather(*[client() for _ in range(int(self.traffic["in_flight"]))])

    async def phase(self, seconds: float, measured: bool) -> None:
        loop = self.open_loop if self.traffic["loop"] == "open" else self.closed_loop
        await loop(seconds, measured)

    async def run(self, reader) -> None:
        import aiohttp

        timeout = aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)
        connector = aiohttp.TCPConnector(limit=int(self.traffic.get("connections", 256)))
        async with aiohttp.ClientSession(timeout=timeout, connector=connector) as self.http:
            await self.phase(float(self.traffic["warm_seconds"]), measured=False)
            self.sent = 0
            sys.stdout.buffer.write(b"ready\n")
            sys.stdout.buffer.flush()
            await reader.readline()  # "go"
            t0 = time.monotonic()
            await self.phase(float(self.job["seconds"]), measured=True)
            self.finish(t0)

    def finish(self, t0: float) -> None:
        rec = np.asarray(self.records, np.float64).reshape(-1, 4)
        ok = rec[:, 2] > 0
        in_window = ok & (rec[:, 3] <= self.t_close)
        arrays = {
            "latency_ms": rec[ok, 0] * 1e3,
            "late_ms": rec[:, 1] * 1e3,
            "done_s": rec[ok, 3] - t0,
        }
        meta = []
        for i, (ordinal, member, body, raw) in enumerate(self.samples):
            arrays[f"resp_{i}"] = np.frombuffer(raw, np.uint8)
            meta.append({"ordinal": ordinal, "member": member, "body": body})
        blob = io.BytesIO()
        np.savez(blob, **arrays)
        summary = {
            "attempted": int(self.sent),
            "failed": int(self.sent - ok.sum()),
            "completed_in_window": int(in_window.sum()),
            "rows_completed_in_window": int(in_window.sum()) * self.rows,
            "window_s": self.t_close - t0,
            "drain_s": time.monotonic() - self.t_close,
            "samples": meta,
            "blob_bytes": blob.getbuffer().nbytes,
        }
        out = sys.stdout.buffer
        out.write(b"done " + json.dumps(summary).encode() + b"\n")
        out.write(blob.getbuffer())
        out.flush()


async def _main() -> None:
    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    job = json.loads(await reader.readline())
    await Generator(job).run(reader)


if __name__ == "__main__":
    asyncio.run(_main())
