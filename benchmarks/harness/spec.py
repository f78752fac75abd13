"""Everything the harness knows about a cell comes from data files found by
the names in ``BENCHMARK.json``: the configuration's file, the traffic mix
``traffic/<traffic>.json``, the cell's own ``cells/<cell>.json`` (limits of
``correct``) and one reader ``layer_metrics/<metric>.py`` per per-layer
metric. Adding a cell, a mix, a configuration or a metric adds files and
``BENCHMARK.json`` entries; no file here is edited."""

import importlib.util
import json
import os
from typing import Any, Callable, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    limits resolved. ``overrides`` (tests only) replaces sizes so a CPU
    rehearsal can run the same code at a tiny size. ``entry`` (tests and
    tools only) stands in for the ``workloads`` entry of a cell whose files
    are here but which ``BENCHMARK.json`` does not list yet (PERF.md
    section 7)."""

    def __init__(self, name: str, root: str = ROOT, overrides: Optional[dict] = None,
                 entry: Optional[dict] = None):
        bench = load_benchmark(root)
        by_name = {w["name"]: w for w in bench["workloads"]}
        if entry is None and name not in by_name:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has {sorted(by_name)}"
            )
        self.name = name
        self.entry = dict(entry or by_name[name], name=name)
        self.chips = int(self.entry.get("chips", 1))
        files = {c["name"]: c["file"] for c in bench["configs"]}
        config = self.entry["config"]
        self.config = _load_json(os.path.join(
            root, files.get(config, os.path.join("benchmarks", "configs", config + ".json"))
        ))
        self.traffic = _load_json(
            os.path.join(BENCH_DIR, "traffic", self.entry["traffic"] + ".json")
        )
        self.limits = _load_json(os.path.join(BENCH_DIR, "cells", name + ".json"))[
            "limits"
        ]
        for section, values in (overrides or {}).items():
            getattr(self, section).update(values)
        like = self.entry.get("metrics_like", name)  # an unlisted cell reports as a listed one does
        self.end_to_end = [m for m in bench["end_to_end"] if like in m.get("workloads", [like])]
        self.per_layer = [m for m in bench["per_layer"] if like in m.get("workloads", [like])]


def load_reader(metric: str) -> Callable[[dict], Optional[float]]:
    """``layer_metrics/<metric>.py`` must define ``read(obs)``: the metric's
    value from the run's observations (spans, counters, trace reduction),
    or ``None`` where it finds nothing to read."""
    path = os.path.join(BENCH_DIR, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of the device; an unlisted device is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"({sorted(table)}): no peak, no roofline"
        )
    return table[device_kind]
