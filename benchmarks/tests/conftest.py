"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
They run on the CPU (Pallas kernels in interpret mode) and never print a
device metric."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)

import pytest  # noqa: E402

TINY_CONFIG = {"tags_per_machine": 12, "gang_members": 6, "bank_members": 6}


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    """Tiny CPU programs must not land in the checkout's chip cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


@pytest.fixture
def tiny_cell(monkeypatch):
    """A cell cut to a size the CPU holds, through the same files."""
    from harness import adapter
    from queued import cell as make_cell

    def make(name: str):
        traffic = {
            "rows": 70, "check_members": 3, "request_rows": 40, "warm_seconds": 0.3,
            "warm_batches": [1, 2, 4], "rate_rps": 30.0, "check_requests": 3,
            "trace_seconds": 0.5,
        }
        cell = make_cell(name, overrides={"config": dict(TINY_CONFIG, epochs=3, batch_size=16),
                                          "traffic": traffic})
        adapter._estimator_kwargs(cell.config["model"]).update(epochs=3, batch_size=16)
        monkeypatch.setenv("GORDO_BANK_KERNEL", "interpret")
        if cell.config["family"] == "lstm":
            monkeypatch.setenv("GORDO_SEQ_LAYOUT", "time_major")
            monkeypatch.setenv("GORDO_SEQ_KERNEL", "interpret")
        return cell

    return make
