#!/usr/bin/env python3
"""One traced run of a cell, read by the program's own stages (run on the
chip; the benchmark never runs this):

    python3 benchmarks/tools/idle_by_stage.py --workload <cell> --seed <n> [--seconds <s>]

The program opens a ``gordo:<stage>`` profiler region around every stage of
its host work (``observability/tracing.py::stage``), so the traced run's
``.xplane.pb`` holds them beside the XLA ops. This tool runs the cell as
``run.py --trace 1`` does and, from the same trace file, prints

- the ledger's own table with the other prefix:
  ``reduce_planes(..., annotations_prefix="gordo:")["idle_gaps"]``, where a
  whole gap goes to the innermost stage that covers all of it;
- device idle seconds inside the benchmark's ``bench:`` span by program
  stage, each idle stretch cut at the stage boundaries (innermost stage
  wins), the lead-in before the first op and the tail after the last one
  included, and the share no stage covers: the program had nothing in hand;
- the stage regions that ran longest against their own median, with the
  host-plane events that overlap them;
- what the open profiler session cost: the part of the window measured
  with the session open against the rest of the same window;
- for a serve cell, the slowest requests the server's reservoir kept, by
  top-level stage.

The numbers land in ``chiprun_out/idle_by_stage/<cell>.<seed>.json`` too,
and every stage region and device program run of the trace, one row each,
in ``<cell>.<seed>.timeline.csv`` beside it. For a refit cell it also prints
the process's first fit (the run's set-up fit) by the trainer's own spans.
"""

import argparse
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

STAGE_PREFIX = "gordo:"
WINDOW_PREFIX = "bench:"
NO_STAGE = "no stage"

Interval = Tuple[float, float]


def _merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _subtract(within: List[Interval], busy: List[Interval]) -> List[Interval]:
    """The parts of ``within`` that no interval of ``busy`` covers (both merged)."""
    out = []
    for s, e in within:
        at = s
        for bs, be in busy:
            if be <= at:
                continue
            if bs >= e:
                break
            if bs > at:
                out.append((at, bs))
            at = max(at, be)
        if at < e:
            out.append((at, e))
    return out


def idle_by_stage(planes, stage_prefix: str = STAGE_PREFIX,
                  window_prefix: str = WINDOW_PREFIX) -> dict:
    """Device idle nanoseconds inside the ``window_prefix`` host spans, by
    the innermost ``stage_prefix`` host span over each piece. ``planes`` as
    ``harness.trace.reduce_planes`` takes them."""
    from harness import trace as trace_mod

    devices, stages, windows = [], [], []
    for plane in planes:
        if plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    devices.append(_merge(
                        [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                    ))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(stage_prefix):
                        stages.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                    elif e.name.startswith(window_prefix):
                        windows.append((e.start_ns, e.start_ns + e.duration_ns))
    if not devices:
        return {}
    windows = _merge(windows)
    by_stage: Dict[str, float] = {}
    idle_ns = window_ns = 0.0
    for busy in devices:
        inside = windows or ([(busy[0][0], busy[-1][1])] if busy else [])
        window_ns += sum(e - s for s, e in inside)
        for s, e in _subtract(inside, busy):
            idle_ns += e - s
            over = [st for st in stages if st[0] < e and st[1] > s]
            cuts = sorted({s, e} | {t for st in over for t in st[:2] if s < t < e})
            for a, b in zip(cuts, cuts[1:]):
                covering = [(he - hs, name) for hs, he, name in over if hs <= a and b <= he]
                label = min(covering)[1] if covering else NO_STAGE
                by_stage[label] = by_stage.get(label, 0.0) + (b - a)
    n = len(devices)
    regions: Dict[str, List[float]] = {}
    for hs, he, name in stages:
        if not windows or any(ws <= hs and he <= we for ws, we in windows):
            regions.setdefault(name, []).append((he - hs) / 1e9)
    return {
        "window_s": window_ns / n / 1e9,
        "idle_s": idle_ns / n / 1e9,
        "by_stage": {
            k: v / n / 1e9 for k, v in sorted(by_stage.items(), key=lambda kv: -kv[1])
        },
        # the host's own time in each stage inside the window, to hold the idle against
        "stage_regions": {
            name: {"count": len(d), "total_s": sum(d), "median_ms": 1e3 * statistics.median(d)}
            for name, d in regions.items()
        },
    }


def stage_outliers(planes, stage_prefix: str = STAGE_PREFIX, keep: int = 5,
                   overlapping: int = 8) -> List[dict]:
    """The stage regions that ran longest against their own stage's median,
    each with the longest other host-plane events that overlap it."""
    stages, others = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                row = (e.start_ns, e.start_ns + e.duration_ns, e.name, line.name)
                (stages if e.name.startswith(stage_prefix) else others).append(row)
    by_name: Dict[str, List[float]] = {}
    for s, e, name, _ in stages:
        by_name.setdefault(name, []).append(e - s)
    medians = {name: statistics.median(d) for name, d in by_name.items()}
    worst = sorted(stages, key=lambda st: -((st[1] - st[0]) - medians[st[2]]))[:keep]
    out = []
    for s, e, name, line in worst:
        over = sorted(
            ((min(e, oe) - max(s, os_), oname, oline)
             for os_, oe, oname, oline in others if os_ < e and oe > s),
            reverse=True,
        )[:overlapping]
        out.append({
            "stage": name, "thread": line, "ms": (e - s) / 1e6,
            "stage_median_ms": medians[name] / 1e6,
            "overlapping": [
                {"ms": d / 1e6, "event": oname[:100], "thread": oline} for d, oname, oline in over
            ],
        })
    return out


def timeline(planes, stage_prefix: str = STAGE_PREFIX,
             window_prefix: str = WINDOW_PREFIX) -> List[list]:
    """Every stage region, benchmark span and device program run of the
    trace as ``[start_ms, duration_ms, name, thread]`` rows from the first
    event on: small enough to bring back and lay side by side by hand."""
    from harness import trace as trace_mod

    rows = []
    for plane in planes:
        device = plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != trace_mod.MODULES_LINE:
                continue
            for e in line.events:
                if device or e.name.startswith((stage_prefix, window_prefix)):
                    name = trace_mod._strip(e.name) if device else e.name
                    rows.append([e.start_ns, e.duration_ns, name, plane.name if device else line.name])
    if not rows:
        return []
    t0 = min(r[0] for r in rows)
    return sorted([(r[0] - t0) / 1e6, r[1] / 1e6, r[2], r[3]] for r in rows)


def _first_fit(top: int = 8) -> Optional[dict]:
    """The process's first fit (the run's set-up fit), by the trainer's
    own spans: seconds by stage, and the longest of the compile spans JAX
    reported inside it. What a first fit pays that a warm one does not."""
    from gordo_components_tpu.observability.tracing import get_tracer

    fits = sorted(
        (t for t in get_tracer().recent() if t.name == "fleet_fit"), key=lambda t: t.root.start
    )
    if not fits:
        return None
    first, compile_spans = fits[0], ("trace_lower", "backend_compile", "cache_load")
    seconds: Dict[str, float] = {}
    for span in first.spans:
        if span.end is not None and span is not first.root:
            seconds[span.name] = seconds.get(span.name, 0.0) + (span.end - span.start)
    longest = sorted(
        ((s.end - s.start, s.name, s.attributes.get("fun_name"), s.start - first.root.start)
         for s in first.spans if s.name in compile_spans and s.end is not None),
        reverse=True,
    )[:top]
    return {
        "wall_s": first.duration_s, "seconds_by_span": seconds,
        "longest_compile_spans": [
            {"s": d, "span": name, "fun_name": fun, "at_s": at} for d, name, fun, at in longest
        ],
    }


def _session_cost(cell_driver: str, values: dict, obs: dict) -> Optional[dict]:
    """The stretch measured with the profiler session open against the
    rest of the same window."""
    if cell_driver == "refit":
        walls = [f["wall_s"] for f in obs["fits"]]
        if len(walls) < 2:
            return None
        rest = statistics.median(walls[1:])
        return {
            "unit": "s a fit", "session_open": walls[0], "session_closed_median": rest,
            "cost_share": walls[0] / rest - 1.0, "fits": len(walls),
            "train_members_per_s": values.get("train_members_per_s"),
        }
    latency = list(obs.get("latency_ms", ()))
    if not latency:
        return None
    # completion order: the traced stretch is the window's first part
    k = int(len(latency) * min(1.0, obs["traced_window_s"] / obs["window_s"]))
    if k < 10 or len(latency) - k < 10:
        return None
    on, off = float(statistics.median(latency[:k])), float(statistics.median(latency[k:]))
    return {
        "unit": "ms p50", "session_open": on, "session_closed_median": off,
        "cost_share": on / off - 1.0, "requests": [k, len(latency) - k],
        "score_p50_ms": values.get("score_p50_ms"), "score_p95_ms": values.get("score_p95_ms"),
    }


def _slow_requests(tracer, keep: int = 8) -> List[dict]:
    """The slowest scoring requests the server's reservoir kept, by
    top-level span (milliseconds)."""
    out = []
    for trace in tracer.slow(keep):
        top: Dict[str, float] = {}
        for span in trace.spans:
            if span is trace.root or getattr(span, "parent", None) is not None:
                continue
            if span.end is not None:
                top[span.name] = top.get(span.name, 0.0) + (span.end - span.start) * 1e3
        out.append({"root": trace.name, "ms": trace.duration_s * 1e3, "stages_ms": top})
    return out


def read_traced_run(run_once) -> dict:
    """``run_once()`` makes one traced run in this process (``run.main``
    with ``--trace 1``; a test drives a tiny cell). While it runs, the
    harness's own calls are tapped: the trace file it reduces is read by
    stage as well, and what it hands to the result line is kept."""
    from harness import common, serve, trace as trace_mod

    seen: dict = {}
    reduce_file, emit, span_ms = trace_mod.reduce_file, common.emit, serve._span_ms

    def reduce_both(path: str) -> dict:
        from jax.profiler import ProfileData

        planes = list(ProfileData.from_file(path).planes)
        seen["ledger_rule"] = trace_mod.reduce_planes(
            planes, annotations_prefix=STAGE_PREFIX
        ).get("idle_gaps")
        seen["idle"] = idle_by_stage(planes)
        seen["outliers"] = stage_outliers(planes)
        reduced = trace_mod.reduce_planes(planes)
        seen["timeline"] = timeline(planes)
        seen["module_calls"] = reduced.get("module_calls")
        seen["module_seconds"] = reduced.get("module_seconds")
        return reduced

    def emit_and_keep(cell, traced, values, obs, *rest):
        seen["cost"] = _session_cost(cell.traffic["driver"], values, obs)
        seen["per_layer"] = common.per_layer_metrics(cell, obs)
        if cell.traffic["driver"] == "refit":
            seen["first_fit"] = _first_fit()
        seen["span_ms"] = {  # serve cells: every span name the server retained
            name: {"count": len(d), "median": statistics.median(d), "mean": statistics.fmean(d)}
            for name, d in (obs.get("spans") or {}).items()
        }
        return emit(cell, traced, values, obs, *rest)

    def span_ms_and_slow(app):
        seen["slow"] = _slow_requests(app["tracer"])
        return span_ms(app)

    trace_mod.reduce_file, common.emit, serve._span_ms = (
        reduce_both, emit_and_keep, span_ms_and_slow
    )
    try:
        seen["exit_code"] = run_once()
    finally:
        trace_mod.reduce_file, common.emit, serve._span_ms = reduce_file, emit, span_ms
    return seen


def report(workload: str, seed: int, seen: dict) -> None:
    idle = seen["idle"]
    print(f"\n{workload} seed {seed}: device idle {idle['idle_s']:.3f} s of "
          f"{idle['window_s']:.3f} s inside {WINDOW_PREFIX}*, by program stage")
    for name, s in idle["by_stage"].items():
        region = idle["stage_regions"].get(name)
        held = (f"  of {region['total_s']:.4f} s the host spent there in {region['count']} regions"
                if region else "")
        print(f"  {s:9.4f} s  {100 * s / idle['idle_s']:5.1f}%  {name}{held}")
    named = idle["idle_s"] - idle["by_stage"].get(NO_STAGE, 0.0)
    print(f"  named stages cover {100 * named / idle['idle_s']:.1f}% of the idle seconds")
    print(f"whole gaps by the stage that covers all of a gap (the ledger's rule): "
          f"{seen['ledger_rule']}")
    print(f"device programs in the traced stretch: calls {seen['module_calls']}, "
          f"seconds {seen['module_seconds']}")
    print(f"profiler session open against closed, same window: {seen['cost']}")
    for row in seen["outliers"]:
        print(f"outlier {row['stage']} {row['ms']:.2f} ms (median {row['stage_median_ms']:.2f}) "
              f"on {row['thread']!r}; overlapping: "
              + "; ".join(f"{o['ms']:.2f} ms {o['event']} [{o['thread']}]"
                          for o in row["overlapping"]))
    if seen.get("first_fit"):
        first = seen["first_fit"]
        print(f"the process's first fit, {first['wall_s']:.2f} s, by span: "
              + ", ".join(f"{k} {v:.3f}" for k, v in sorted(
                  first["seconds_by_span"].items(), key=lambda kv: -kv[1])[:14]))
        for row in first["longest_compile_spans"]:
            print(f"  {row['s']:.3f} s {row['span']} {row['fun_name']} at {row['at_s']:.2f} s")
    for name, row in sorted(seen["span_ms"].items(), key=lambda kv: -kv[1]["mean"]):
        print(f"span {name}: median {row['median']:.3f} ms, mean {row['mean']:.3f} ms, "
              f"{row['count']} retained")
    for row in seen.get("slow", ()):
        stages = sorted(row["stages_ms"].items(), key=lambda kv: -kv[1])[:5]
        print(f"slow {row['root']} {row['ms']:.2f} ms: "
              + ", ".join(f"{k} {v:.2f}" for k, v in stages))


def write(out_dir: str, workload: str, seed: int, seen: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    stem = os.path.join(out_dir, f"{workload}.{seed}")
    with open(stem + ".timeline.csv", "w") as fh:
        fh.write("start_ms,duration_ms,name,thread\n")
        for start, duration, name, thread in seen.pop("timeline"):
            fh.write(f"{start:.4f},{duration:.4f},{name},{thread}\n")
    with open(stem + ".json", "w") as fh:
        json.dump(dict(seen, workload=workload, seed=seed), fh, indent=1)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    args = parser.parse_args(argv)

    import run as run_cell
    from harness import spec

    run_argv = ["--workload", args.workload, "--seed", str(args.seed), "--trace", "1"]
    if args.seconds is not None:
        run_argv += ["--seconds", str(args.seconds)]
    seen = read_traced_run(lambda: run_cell.main(run_argv))
    if seen["exit_code"] or not seen.get("idle"):
        print("no device trace was read", file=sys.stderr)
        return seen["exit_code"] or 1
    report(args.workload, args.seed, seen)
    write(os.path.join(spec.ROOT, "chiprun_out", "idle_by_stage"), args.workload, args.seed, seen)
    return 0


if __name__ == "__main__":
    sys.exit(main())
