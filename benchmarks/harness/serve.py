"""Cells whose traffic is scoring requests over HTTP.

Set-up writes the bank's artifact tree (``adapter.write_artifacts``),
builds the product's app on a real localhost port, awaits the bank's
warm-up compile of exactly this mix's shapes, and lets the load generator
(a child process, ``loadgen.py``) send an unmeasured burst. The window is
the generator's; this process only serves, snapshots the server's counters
around it and, in a traced run, profiles a stretch of it. After the window:
peak memory is read, the server and its bank are dropped, and a seeded
sample of the answers the window produced is compared with the plain
reference.
"""

import asyncio
import gc
import io
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from harness import adapter, check, common, reference, spec, weights, wire

COMPARED = (
    "model-output", "tag-anomaly-unscaled", "tag-anomaly-scaled",
    "total-anomaly-unscaled", "total-anomaly-scaled",
)


def compare_answers(config: dict, seed: int, rows: int, samples: List[dict],
                    answers: List[Dict[str, np.ndarray]], **how) -> Dict[str, float]:
    """Each sampled answer against the reference run once over the same
    request for the same member (weights and body remade from the seed)."""
    empty = 0.0 if answers else float("inf")  # nothing compared proves nothing
    numbers = {"input_echo_gap": empty, "output_gap": empty, "score_gap": empty}
    for meta, got in zip(samples, answers):
        w = weights.member_weights(config, seed, meta["member"])
        X = weights.request_body(config, seed, meta["body"], rows)
        want = reference.anomaly(config, w, X, **how)
        numbers["input_echo_gap"] = max(
            numbers["input_echo_gap"], check.sup_gap(got["model-input"], want["model-input"])
        )
        numbers["output_gap"] = max(
            numbers["output_gap"], check.rel_l2_gap(got["model-output"], want["model-output"])
        )
        for name in COMPARED[1:]:
            numbers["score_gap"] = max(numbers["score_gap"], check.rel_l2_gap(got[name], want[name]))
    return numbers


def _warmup_env(traffic: dict, traced: bool) -> None:
    """The bank warms exactly the (rows, batch) shapes this mix can reach."""
    os.environ["GORDO_WARMUP_ROWS"] = str(int(traffic["request_rows"]))
    os.environ["GORDO_WARMUP_BATCHES"] = ",".join(str(b) for b in traffic["warm_batches"])
    if traced:
        os.environ["GORDO_TRACE_SAMPLE"] = "1"
        os.environ["GORDO_TRACE_RING"] = "1000000"


async def _engine_stats(http, base: str) -> dict:
    async with http.get(f"{base}/stats") as resp:
        return (await resp.json())["bank_engine"]


def _span_ms(app) -> Dict[str, List[float]]:
    """Durations of the server's own stage spans, by name, over every
    retained request trace."""
    out: Dict[str, List[float]] = {}
    for tr in app["tracer"].recent():
        for span in tr.spans:
            if span.end is not None:
                out.setdefault(span.name, []).append((span.end - span.start) * 1e3)
    return out


class Served:
    """The product's app on a localhost port, warm, with the counters and
    spans a window is read through."""

    def __init__(self, cell: spec.Cell, seed: int, app, runner, base: str, work: str):
        self.cell, self.seed, self.app, self.runner = cell, seed, app, runner
        self.base, self.work = base, work
        self.n_members = int(cell.config["bank_members"])

    async def window(self, traffic: dict, seconds: float, t_start: Optional[float] = None,
                     traced: bool = False, seed: Optional[int] = None) -> dict:
        """One generator process: its set-up and unmeasured burst, then the
        measured window. ``t_start`` (the run's own window) stamps
        ``setup_s`` the moment the window opens."""
        from aiohttp import ClientSession

        job = {
            "base_url": self.base, "n_members": self.n_members,
            "seed": self.seed if seed is None else seed,
            "seconds": seconds, "traffic": traffic,
            "config": {"tags_per_machine": self.cell.config["tags_per_machine"]},
        }
        child = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(spec.BENCH_DIR, "harness", "loadgen.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE, limit=2**20,
        )
        try:
            child.stdin.write(json.dumps(job).encode() + b"\n")
            await child.stdin.drain()
            line = await child.stdout.readline()
            if line.strip() != b"ready":
                raise RuntimeError(f"load generator said {line!r}")
            async with ClientSession() as http:
                before = await _engine_stats(http, self.base)
                window = common.TracedWindow(os.path.join(self.work, "trace")) if traced else None
                loop = asyncio.get_running_loop()
                setup_s = None if t_start is None else time.time() - t_start
                full_passes = gc.get_stats()[2]["collections"]  # a counter; nothing is run
                child.stdin.write(b"go\n")
                await child.stdin.drain()
                if window is not None:
                    await loop.run_in_executor(None, window.start)
                    with common.annotate("window"):
                        await asyncio.sleep(min(float(traffic["trace_seconds"]), seconds))
                    await loop.run_in_executor(None, window.stop)
                line = await child.stdout.readline()
                if not line.startswith(b"done "):
                    raise RuntimeError(f"load generator said {line[:200]!r}")
                summary = json.loads(line[5:])
                full_passes = gc.get_stats()[2]["collections"] - full_passes
                blob = await child.stdout.readexactly(summary["blob_bytes"])
                await child.wait()
                after = await _engine_stats(http, self.base)
        finally:
            if child.returncode is None:
                child.kill()
                await child.wait()
        return dict(
            summary=summary, blob=blob, setup_s=setup_s, window=window,
            engine={k: after[k] - before[k] for k in ("batches", "requests")},
            queue_depth=after.get("queue_depth"), full_collections=full_passes,
        )


async def start_server(cell: spec.Cell, seed: int, traced: bool, on_tpu: bool, work: str) -> Served:
    from aiohttp import web

    config, traffic = cell.config, cell.traffic
    n_members = int(config["bank_members"])
    _warmup_env(traffic, traced)
    model_dir = os.path.join(work, "models")
    t0 = time.time()
    adapter.write_artifacts(config, seed, n_members, model_dir)
    app = adapter.build_server_app(model_dir)
    # The bank build leaves the unpickled members' garbage (some 10^6
    # objects) for the collector's next full pass, 150 ms that fell at chance
    # inside or outside the first minute of serving. One pass here, where the
    # build ends and where the program's own cure (``gc.freeze()`` after the
    # build, PERF.md section 7) would sit, and none after it: whatever the
    # collector does from warm-up on counts in the window.
    gc.collect()
    t_build = time.time() - t0
    runner = web.AppRunner(app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        t1 = time.time()
        await app["warmup_future"]
        decisions = adapter.check_serving_decisions(
            app, n_members, adapter.tpu_decisions(config, "serve") if on_tpu else None
        )
    except BaseException:
        await runner.cleanup()
        raise
    print(f"serve: {n_members} members, artifacts + build_app {t_build:.1f}s, "
          f"warm-up {time.time() - t1:.1f}s, port {port}, decisions {decisions}", flush=True)
    return Served(cell, seed, app, runner, f"http://127.0.0.1:{port}/gordo/v0/bench", work)


async def _serve(cell: spec.Cell, seed: int, seconds: float, traced: bool,
                 t_start: float, on_tpu: bool, work: str):
    served = await start_server(cell, seed, traced, on_tpu, work)
    try:
        got = await served.window(cell.traffic, seconds, t_start, traced)
        got["memory_peak"] = common.memory_peak_bytes()
        got["spans"] = _span_ms(served.app)
    finally:
        await served.runner.cleanup()
    return got


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        on_tpu: bool = True) -> dict:
    work = common.work_dir()
    try:
        got = asyncio.run(_serve(cell, seed, seconds, traced, t_start, on_tpu, work))
        gc.collect()  # the app, its bank and the stacked weights are unreferenced now
        summary = got["summary"]
        arrays = np.load(io.BytesIO(got["blob"]))
        answers = [
            wire.unpack(arrays[f"resp_{i}"].tobytes()) for i in range(len(summary["samples"]))
        ]
        rows = int(cell.traffic["request_rows"])
        t_ref = time.monotonic()
        numbers = compare_answers(cell.config, seed, rows, summary["samples"], answers)
        numbers["answers_compared"] = float(len(answers))
        print(f"reference over {len(answers)} answers: {time.monotonic() - t_ref:.2f}s", flush=True)
        checks = check.verdict(numbers, cell.limits)
        latency = arrays["latency_ms"]
        late = arrays["late_ms"]
        if len(latency):
            print("latency percentiles [50, 90, 95, 99, 99.9] ms: "
                  f"{[round(float(v), 3) for v in np.percentile(latency, [50, 90, 95, 99, 99.9])]}")
        print(f"the server's collector made {got['full_collections']} full passes inside the window")
        print(f"window: attempted {summary['attempted']} failed {summary['failed']} "
              f"completed in window {summary['completed_in_window']} "
              f"drain {summary['drain_s']:.2f}s, generator late p99 "
              f"{np.percentile(late, 99) if len(late) else float('nan'):.2f} ms", flush=True)
        values = {"setup_s": got["setup_s"]}
        if len(latency):
            values["score_p50_ms"] = float(np.percentile(latency, 50))
            values["score_p95_ms"] = float(np.percentile(latency, 95))
        values["score_rows_per_s"] = summary["rows_completed_in_window"] / summary["window_s"]
        obs = {
            "config": cell.config, "traffic": cell.traffic, "window_s": summary["window_s"],
            "spans": got["spans"], "late_ms": late, "latency_ms": latency,
            "engine": got["engine"],
            "rows_completed": summary["rows_completed_in_window"],
            "requests_completed": summary["completed_in_window"],
            "request_rows": rows,
        }
        if traced:
            obs["trace"] = got["window"].reduce()
            obs["traced_window_s"] = got["window"].window_s
            obs["peaks"] = spec.peaks_for(common.device_block()["kind"]) if obs["trace"] else None
    finally:
        common.remove(work)
    return common.emit(
        cell, traced, values, obs, summary["attempted"], summary["failed"], checks,
        got["memory_peak"],
    )


def control_readings(cell: spec.Cell, seeds) -> List[dict]:
    """``control.py`` for a serve cell: as many answers as a run compares,
    for members and bodies drawn from each seed, computed by the control
    (the reference in bfloat16) and by the reference at ``highest``, each
    read against the reference. The program's own readings are the
    ``checks`` every run prints; no server is started here."""
    rows = int(cell.traffic["request_rows"])
    n_members = int(cell.config["bank_members"])
    out = []
    for seed in seeds:
        rng = weights.rng_for(seed, weights.SAMPLE)
        samples = [
            {"member": int(rng.integers(n_members)), "body": k}
            for k in range(int(cell.traffic["check_requests"]))
        ]
        row = {"seed": seed}
        for label, how in (
            ("control_bf16", dict(dtype="bfloat16")),
            ("highest", dict(precision="highest")),
        ):
            answers = [
                reference.anomaly(
                    cell.config, weights.member_weights(cell.config, seed, m["member"]),
                    weights.request_body(cell.config, seed, m["body"], rows), **how,
                )
                for m in samples
            ]
            row[label] = compare_answers(cell.config, seed, rows, samples, answers)
        print(json.dumps(row), flush=True)
        out.append(row)
    return out
