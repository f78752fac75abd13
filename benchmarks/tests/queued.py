"""Cells whose files are under ``benchmarks/`` but which ``BENCHMARK.json``
does not list yet (PERF.md section 7): the tests keep their reference,
limits and harness path working for the PR that lists them."""

from harness import spec

QUEUED = {
    "lstm300.backfill": {
        "config": "lstm300", "traffic": "backfill", "chips": 1, "metrics_like": "dense300.live",
    },
}
LISTED = [w["name"] for w in spec.load_benchmark()["workloads"]]


def cell(name: str, overrides=None) -> spec.Cell:
    return spec.Cell(name, overrides=overrides, entry=QUEUED.get(name))
