#!/usr/bin/env python3
"""Where each request of a trunk cell's window spent its time, on the chip:
one server, the mix's generator for ``--seconds`` at the cell's rate under
each of ``--seeds`` (every seed replays the same gaps in another order:
``harness/loadgen.py``), then optionally a sweep of ``--sweep`` rates at 10 s
each on the same server.

    python3 benchmarks/tools/latent_trunk_requests.py --workload axk1_trunk300.week \
        --seeds 2300600003,2300600010 --sweep 1.2,1.4,1.6,1.8,2.0

Per window: every request in the order it was due, the client's latency
beside the server's own stage spans (the k-th request by due time is the
k-th trace by root start: one connection each, sent when due); how many
waited behind another call (``queue_wait`` over 10 ms) and the median of
those that did and did not; the median a single server replaying the same
due times would give, with the interval between two back-to-back dispatches
as its service time and an unqueued request's median as its base; calls and
requests of the engine. Before the first window: how many requests the bank
lets one call carry and the bytes it reckons with. (The chip's machine
keeps no scheduler statistics: ``/proc/self/task/*/schedstat`` and
``/proc/stat`` read zeros there, PERF.md section 7.4.) Lines of JSON go to
``chiprun_out/requests_<workload>.jsonl``.
"""

import argparse
import asyncio
import io
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)

STAGES = ("parse", "queue_wait", "device_execute", "postprocess", "resolve", "encode")
WAITED_MS = 10.0


def replay(due, service_s, base_ms):
    """Latencies (ms) of one server that takes the requests in the order
    they are due, ``service_s`` each, ``base_ms`` for one that waits for none."""
    free, out = 0.0, []
    for t in due:
        start = max(t, free)
        free = start + service_s
        out.append((start - t) * 1e3 + base_ms)
    return out


def window_rows(arrays, traces):
    """One row a request, in the order due: client numbers from the
    generator's records, stage spans from the server's trace of it."""
    import numpy as np

    latency, done = arrays["latency_ms"], arrays["done_s"]
    order = np.argsort(done - latency / 1e3)
    traces = sorted(traces, key=lambda tr: tr.root.start)
    rows = []
    for k, i in enumerate(order):
        row = {"due_s": float(done[i] - latency[i] / 1e3), "client_ms": float(latency[i])}
        if len(traces) == len(order):
            tr = traces[k]
            row["server_ms"] = tr.root.duration_s * 1e3
            row["dispatched_s"] = None
            for span in tr.spans:
                if span.parent is None and span.name in STAGES:
                    row[span.name] = row.get(span.name, 0.0) + span.duration_s * 1e3
                if span.name == "device_execute":
                    row["dispatched_s"] = span.start
        rows.append(row)
    return rows


def summarise(rows, seed, rate):
    import numpy as np

    med = lambda values: float(np.median(values)) if len(values) else None
    client = [r["client_ms"] for r in rows]
    out = {"seed": seed, "rate_rps": rate, "requests": len(rows), "p50_ms": med(client),
           "p90_ms": float(np.percentile(client, 90))}
    if rows and "queue_wait" in rows[0]:
        waited = [r for r in rows if r["queue_wait"] > WAITED_MS]
        alone = [r for r in rows if r["queue_wait"] <= WAITED_MS]
        # the interval between two dispatches where the second request was already waiting
        gaps = [b["dispatched_s"] - a["dispatched_s"] for a, b in zip(rows, rows[1:])
                if b["queue_wait"] > WAITED_MS and b["dispatched_s"] > a["dispatched_s"]]
        out.update(
            waited=len(waited), p50_waited_ms=med([r["client_ms"] for r in waited]),
            p50_alone_ms=med([r["client_ms"] for r in alone]),
            back_to_back_s=med(gaps), device_execute_ms=med([r["device_execute"] for r in rows]),
            device_execute_range_ms=[min(r["device_execute"] for r in rows),
                                     max(r["device_execute"] for r in rows)],
        )
        for name in STAGES:
            out[name + "_ms"] = med([r.get(name, 0.0) for r in rows])
        if gaps and alone:
            again = replay([r["due_s"] for r in rows], out["back_to_back_s"], out["p50_alone_ms"])
            out["replayed_p50_ms"] = med(again)
            out["replayed_p90_ms"] = float(np.percentile(again, 90))
    return out


async def drive(cell, seeds, seconds, sweep_rates, on_tpu=True):
    import jax
    import numpy as np

    from harness import common, trunk_serve

    work = common.work_dir()
    lines = []
    try:
        served = await trunk_serve.start_server(cell, seeds[0], True, on_tpu, work)
        try:
            bank, tracer = served.app["bank"], served.app["tracer"]
            rows = int(cell.traffic["request_rows"])
            (bucket,) = [b for b in bank._buckets.values() if b.shared is not None]
            stats = jax.devices()[0].memory_stats() or {}
            head = {
                "batch_limit": bank.batch_limit(bucket.names[0], rows), "free_bytes": bucket._free_bytes,
                "program_bytes": [bucket._module.program_bytes(b, bucket.rows_per_call(rows, bank.max_rows))
                                  for b in (1, 2)],
                "bytes_limit": stats.get("bytes_limit"), "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"), "cores": os.cpu_count(),
                "warm_batches": cell.traffic["warm_batches"],
            }
            print(json.dumps(head), flush=True)
            lines.append(head)
            plan = [(float(cell.traffic["rate_rps"]), seconds, s) for s in seeds]
            plan += [(r, 10.0, seeds[0] + 1000 * (i + 1)) for i, r in enumerate(sweep_rates)]
            for rate, length, seed in plan:
                t_open = time.monotonic()
                # no answers kept: nothing is compared here, and four of them are 192 MB
                traffic = dict(cell.traffic, rate_rps=rate, check_requests=0)
                got = await served.window(traffic, length, seed=seed)
                arrays = np.load(io.BytesIO(got["blob"]))
                # the window's own requests: the warm burst ended before it opened
                traces = [tr for tr in tracer.recent() if tr.name == "anomaly"
                          and tr.root.start >= t_open]
                burst = max(1, int(round(rate * float(cell.traffic["warm_seconds"]))))
                traces = sorted(traces, key=lambda tr: tr.root.start)[burst:]
                table = window_rows(arrays, traces)
                line = summarise(table, seed, rate)
                line.update(
                    seconds=length, drain_s=got["summary"]["drain_s"], failed=got["summary"]["failed"],
                    engine=got["engine"], max_batch_seen=served.app["bank_engine"].stats["max_batch_seen"],
                )
                if length >= seconds:
                    for r in table:
                        print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                                          for k, v in r.items() if k != "dispatched_s"}))
                print(json.dumps(line), flush=True)
                lines.append(line)
        finally:
            await served.runner.cleanup()
    finally:
        common.remove(work)
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="axk1_trunk300.week")
    parser.add_argument("--seeds", default="2300600003")
    parser.add_argument("--seconds", type=float, default=50.0)
    parser.add_argument("--sweep", default="", help="comma-separated requests/s, 10 s each, after the windows")
    args = parser.parse_args(argv)

    from harness import adapter, common, spec

    cell = spec.Cell(args.workload)
    adapter.compile_cache_dir()
    print(f"device {common.device_block()}", flush=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.sweep.split(",") if r]
    lines = asyncio.run(drive(cell, seeds, args.seconds, rates))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"requests_{args.workload}.jsonl"), "a") as fh:
        for line in lines:
            fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
