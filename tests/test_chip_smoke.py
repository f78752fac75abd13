"""``chip_smoke.py`` rehearsed without the chip (on-chip-measurement §2).

The script's phases are importable functions taking sizes, so the same
code that runs ``build-fleet`` -> ``build_app`` -> HTTP scoring on the TPU
runs here on the CPU at a tiny size with the Pallas kernels in interpret
mode — wrong paths, arguments and control flow are found before any chip
time is spent. What only the entry point decides is tested too: it never
prints ``"ok": true`` for a platform other than ``tpu``, a phase that
raises gives a non-zero exit and no result line, and the four-chip option
runs on four (virtual) devices.

The rehearsal trains the tiny gang once for the module (~20 s of CPU
compiles) and every phase test reads it. Only the variant that needs a
process with exactly four devices carries ``slow``.
"""

import asyncio
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(
    n_dense=4, n_lstm=2, n_tags=3, rows=48, epochs=1, request_rows=32, burst=4
)
# the CPU stand-ins for the TPU's decisions: same kernels, interpreted
CPU_EXPECT = dict(
    kernel="interpret", seq_kernel="interpret", seq_layout="time_major"
)
CPU_ENV = {
    "GORDO_BANK_KERNEL": "interpret",
    "GORDO_SEQ_KERNEL": "interpret",
    "GORDO_SEQ_LAYOUT": "time_major",
    "GORDO_WARMUP_ROWS": str(TINY["request_rows"]),
}


@pytest.fixture(scope="module")
def cpu_decisions():
    patch = pytest.MonkeyPatch()
    for key, value in CPU_ENV.items():
        patch.setenv(key, value)
    yield
    patch.undo()


@pytest.fixture(scope="module")
def trained(cpu_decisions, tmp_path_factory):
    """Phase 1 once for the module: ``build-fleet`` through the CLI on the
    tiny gang (the rig's default trainer mesh spans its 8 virtual
    devices)."""
    return chip_smoke.phase_train(
        str(tmp_path_factory.mktemp("chip-smoke")), TINY, seed=0,
        expect=CPU_EXPECT,
    )


def test_train_phase_builds_the_gang_through_the_cli(trained):
    import jax

    assert trained["n_built"] == 6 and trained["n_failed"] == 0
    assert len(trained["dense"]) == 4 and len(trained["lstm"]) == 2
    assert trained["device"]["count"] == len(jax.devices())
    layouts = {b["model_type"]: b["layout"] for b in trained["buckets"]}
    assert layouts == {"AutoEncoder": "legacy", "LSTMAutoEncoder": "time_major"}


def test_serve_and_score_phases_at_tiny_size_on_cpu(trained, capsys):
    """serve -> score end to end over HTTP; every check the chip run makes
    (coverage, health, kernel/layout provenance, coalescing, agreement
    with the per-model path) is made here against the interpreted
    kernels."""
    asyncio.run(
        chip_smoke.phase_serve_and_score(trained, TINY, seed=0, expect=CPU_EXPECT)
    )
    out = capsys.readouterr().out
    assert "banked=6/6 fallback={} kernel=interpret" in out
    assert "seq_layout=time_major seq_kernel=interpret" in out
    assert "engine coalescing moved" in out
    assert "agree with the per-model path" in out
    assert '"ok"' not in out  # only main() prints the result line


def test_a_wrong_device_decision_fails_the_run(trained, monkeypatch):
    """With the TPU's expectations and the CPU's decisions (here the jnp
    epilogue) the run must fail, not pass quietly: that is the silent
    degrade the script exists to catch."""
    monkeypatch.setenv("GORDO_BANK_KERNEL", "jnp")
    with pytest.raises(AssertionError, match="bank kernel 'jnp'"):
        asyncio.run(
            chip_smoke.phase_serve_and_score(
                trained, TINY, seed=0, expect=chip_smoke.TPU_EXPECT
            )
        )


def test_sharded_phase_on_the_virtual_mesh(trained, capsys):
    """The ``--four-chips`` phase on this rig's virtual devices: the bank
    shards over all of them with an equal slice of every bucket on each,
    and agrees bitwise with a single-device bank."""
    import jax

    n = len(jax.devices())
    asyncio.run(
        chip_smoke.phase_four_chips(
            trained, TINY, seed=0, expect=CPU_EXPECT, n_devices=n
        )
    )
    out = capsys.readouterr().out
    assert f"stacked state on {n} distinct devices" in out
    assert "sharded bank == single-device bank (device 0) bitwise" in out


def test_main_refuses_off_tpu(capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""  # no result, not even a partial one
    assert "needs 1 TPU chip" in out.err


def _fake_tpu(monkeypatch, tmp_path, count=1):
    import jax

    import gordo_components_tpu.utils as utils

    device = types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"peak_bytes_in_use": 1},
    )
    for key in CPU_ENV:  # the module's rehearsal settings: default here
        monkeypatch.delenv(key, raising=False)
    monkeypatch.setattr(jax, "devices", lambda: [device] * count)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        utils, "resolve_compile_cache", lambda knob=None: str(tmp_path)
    )


def test_main_exits_nonzero_when_a_phase_raises(monkeypatch, tmp_path, capsys):
    _fake_tpu(monkeypatch, tmp_path)

    def broken(*a, **k):
        raise RuntimeError("warm-up compile failed")

    monkeypatch.setattr(chip_smoke, "run", broken)
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out
    assert "FAILED: RuntimeError: warm-up compile failed" in out


@pytest.mark.parametrize("argv,count", [([], 1), (["--four-chips"], 4)])
def test_last_line_is_the_result_object(monkeypatch, tmp_path, capsys, argv, count):
    """On a TPU with every phase passing, the LAST stdout line is exactly
    the result object, with the device as JAX reports it."""
    _fake_tpu(monkeypatch, tmp_path, count=count)
    calls = []
    monkeypatch.setattr(
        chip_smoke, "run", lambda *a, **k: calls.append((a, k))
    )
    assert chip_smoke.main(argv) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": count},
    }
    assert sum('"ok"' in ln for ln in lines) == 1
    (args, kwargs), = calls
    assert args[0] == chip_smoke.FULL_SIZES and args[2] == chip_smoke.TPU_EXPECT
    assert kwargs["four_chips"] is (count == 4)
    assert "modes: bank_kernel=pallas seq_layout=time_major seq_kernel=pallas" in lines


def test_four_chips_option_needs_four(monkeypatch, tmp_path, capsys):
    _fake_tpu(monkeypatch, tmp_path, count=1)
    assert chip_smoke.main(["--four-chips"]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.slow
def test_four_chip_option_on_exactly_four_virtual_devices():
    """``run(four_chips=True)`` in a process with exactly four (virtual)
    devices, as on the four-chip host: the gang trains over a four-device
    mesh, the bank shards four ways with M/4 members on each device, and
    agrees bitwise with a single-device bank."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        **CPU_ENV,
    )
    code = (
        "import chip_smoke; chip_smoke.run("
        f"{TINY!r}, seed=0, expect={CPU_EXPECT!r}, four_chips=True)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "'count': 4" in proc.stdout
    assert "stacked state on 4 distinct devices" in proc.stdout
    assert "sharded bank == single-device bank (device 0) bitwise" in proc.stdout
