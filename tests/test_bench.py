"""``bench.py`` is one process that measures the chip or says it cannot.

No supervisor, no probe children, no CPU re-run under the same metric
names: off-TPU the run exits non-zero unless ``--platform cpu`` asks for the
functional rehearsal explicitly (which prints no measured value), and a
metric that raises makes the run exit non-zero."""

import importlib.util
import json
import os
import sys

import pytest

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py")


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # main() places the compile cache like every entry point; keep this
    # process's cache config out of the test
    import gordo_components_tpu.utils as utils

    monkeypatch.setattr(utils, "resolve_compile_cache", lambda knob=None: None)
    return mod


def _run_main(bench, monkeypatch, capsys, *argv):
    monkeypatch.setattr(sys, "argv", ["bench.py", *argv])
    rc = bench.main()
    out = capsys.readouterr()
    return rc, out.out.splitlines(), out.err


def test_exits_nonzero_off_tpu_without_explicit_cpu(bench, monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(
        bench, "METRICS", (("fleet", lambda: ran.append(1) or {"x": 1}),)
    )
    rc, lines, err = _run_main(bench, monkeypatch, capsys)
    assert rc != 0
    assert ran == []  # nothing is measured on a platform that is not the TPU
    assert lines == []  # and no result line is printed
    assert "--platform cpu" in err


def test_explicit_cpu_rehearsal_prints_names_not_values(
    bench, monkeypatch, capsys
):
    monkeypatch.setattr(
        bench,
        "METRICS",
        (("fleet", lambda: {"fleet_models_per_hour_per_chip": 123456.0}),),
    )
    rc, lines, _ = _run_main(bench, monkeypatch, capsys, "--platform", "cpu")
    assert rc == 0
    (metric_line,) = [ln for ln in lines if ln.startswith("METRIC fleet ")]
    record = json.loads(metric_line.split(" ", 2)[2])
    assert record == {
        "platform": "cpu",
        "reports": ["fleet_models_per_hour_per_chip"],
    }
    headline = json.loads(lines[-1])
    assert headline["platform"] == "cpu" and headline["value"] is None
    assert "123456" not in "\n".join(lines)  # no number under a device name
    assert "mfu" not in headline


def test_raising_metric_gives_nonzero_exit_and_later_metrics_still_run(
    bench, monkeypatch, capsys
):
    def boom():
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(
        bench, "METRICS", (("fleet", boom), ("sequential", lambda: {"y": 2}))
    )
    rc, lines, _ = _run_main(bench, monkeypatch, capsys, "--platform", "cpu")
    assert rc != 0
    err = json.loads(
        next(ln for ln in lines if ln.startswith("METRIC_ERROR ")).split(" ", 1)[1]
    )
    assert err == {"name": "fleet", "error": "RuntimeError: kernel refused"}
    assert any(ln.startswith("METRIC sequential ") for ln in lines)
    assert json.loads(lines[-1])["errors"] == {
        "fleet": "RuntimeError: kernel refused"
    }


def test_order_and_skip_select_metrics_and_reject_unknown(
    bench, monkeypatch, capsys
):
    ran = []
    monkeypatch.setattr(
        bench,
        "METRICS",
        tuple((n, lambda n=n: ran.append(n) or {}) for n in ("a", "b", "c")),
    )
    rc, _, _ = _run_main(
        bench, monkeypatch, capsys,
        "--platform", "cpu", "--order", "c,a,b", "--skip", "a",
    )
    assert rc == 0 and ran == ["c", "b"]
    monkeypatch.setattr(sys, "argv", ["bench.py", "--platform", "cpu", "--order", "zz"])
    with pytest.raises(SystemExit, match="unknown metric"):
        bench.main()


def test_tool_children_are_pinned_to_the_cpu(bench):
    """The bench process holds the chip: every tools/*_demo.py child gets
    JAX_PLATFORMS=cpu, and those legs' records say so."""
    assert bench._cpu_tool_env()["JAX_PLATFORMS"] == "cpu"
    assert bench.CPU_TOOL_METRICS <= {n for n, _ in bench.METRICS}
