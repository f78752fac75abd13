"""Profiling/observability tests: traces only when enabled, memory stats
shape, and the structured timing that now lands in build metadata."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from gordo_components_tpu.utils.profiling import device_memory_stats, maybe_profile


def test_maybe_profile_off_is_free(monkeypatch):
    monkeypatch.delenv("GORDO_PROFILE_DIR", raising=False)
    with maybe_profile("noop"):
        pass  # no jax import, no trace dir


def test_maybe_profile_writes_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    with maybe_profile("unit trace/x", profile_dir=str(tmp_path)):
        jnp.ones((8, 8)).sum().block_until_ready()
    # sanitized name, non-empty trace directory
    out = tmp_path / "unit-trace-x"
    assert out.is_dir()
    assert any(out.rglob("*")), "profiler should have written trace files"


def test_maybe_profile_env_activation(tmp_path, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("GORDO_PROFILE_DIR", str(tmp_path))
    with maybe_profile("envtrace"):
        jnp.ones((4,)).sum().block_until_ready()
    assert (tmp_path / "envtrace").is_dir()


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    # CPU backends may report nothing; whatever is reported must be ints
    for dev, s in stats.items():
        assert isinstance(dev, str)
        for v in s.values():
            assert isinstance(v, int)


def test_fleet_stats_include_epoch_seconds():
    from gordo_components_tpu.parallel.fleet import FleetTrainer

    rng = np.random.RandomState(0)
    members = {f"m-{i}": rng.rand(40, 3).astype("float32") for i in range(2)}
    trainer = FleetTrainer(epochs=3, batch_size=20)
    trainer.fit(members)
    (bucket,) = trainer.last_stats["buckets"]
    assert len(bucket["epoch_seconds"]) == 3
    assert all(t >= 0 for t in bucket["epoch_seconds"])


def test_build_metadata_has_device_memory(tmp_path):
    from gordo_components_tpu.builder import build_model

    _, meta = build_model(
        "prof-m",
        {"gordo_components_tpu.models.AutoEncoder": {"epochs": 1, "batch_size": 32}},
        {
            "type": "RandomDataset",
            "train_start_date": "2020-01-01T00:00:00Z",
            "train_end_date": "2020-01-01T04:00:00Z",
            "tag_list": ["a", "b"],
        },
    )
    assert "device_memory" in meta["model"]


@pytest.fixture
def cache_config():
    """Restore the process-wide compile-cache config a test changed (the
    suite runs with the persistent cache off, tests/conftest.py)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {
        k: getattr(jax.config, k)
        for k in (
            "jax_compilation_cache_dir",
            "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
        )
    }
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_enable_compile_cache_persists_programs(tmp_path, cache_config):
    """The persistent XLA cache must actually capture compiled programs:
    a restarted builder pod's recompiles become disk reads. min=0 so even
    this test's tiny program is cached."""
    import jax

    from gordo_components_tpu.utils import enable_compile_cache

    cache_dir = str(tmp_path / "xla-cache")
    jax.config.update("jax_enable_compilation_cache", True)
    out = enable_compile_cache(cache_dir, min_compile_seconds=0.0)
    assert out == cache_dir and os.path.isdir(cache_dir)
    assert jax.config.jax_compilation_cache_dir == cache_dir

    @jax.jit
    def f(x):
        return (x @ x).sum() * 3.0

    f(jnp.ones((64, 64))).block_until_ready()
    assert len(os.listdir(cache_dir)) >= 1  # a program landed on disk


def test_cli_compile_cache_option(tmp_path, cache_config, monkeypatch):
    import jax
    from click.testing import CliRunner

    from gordo_components_tpu.cli.cli import gordo

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = str(tmp_path / "cli-cache")
    # any cheap subcommand exercises the group callback; workflow
    # generate needs no devices
    cfg = tmp_path / "fleet.yaml"
    cfg.write_text(
        "machines:\n"
        "  - name: cc-m1\n"
        "    dataset:\n"
        "      type: RandomDataset\n"
        "      train_start_date: 2020-01-01T00:00:00Z\n"
        "      train_end_date: 2020-01-02T00:00:00Z\n"
        "      tag_list: [t1, t2]\n"
    )
    res = CliRunner().invoke(
        gordo,
        ["--compile-cache-dir", cache_dir, "workflow", "generate",
         "-f", str(cfg), "-p", "ccproj"],
    )
    assert res.exit_code == 0, res.output
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert os.path.isdir(cache_dir)


# ------------------------------------------------------------------ #
# resolve_compile_cache: where the persistent cache lives
# ------------------------------------------------------------------ #


@pytest.fixture
def no_config_update(monkeypatch):
    """Fail the test if the code under it touches jax's cache-dir config
    (the env-wins rule: JAX already uses JAX_COMPILATION_CACHE_DIR)."""
    import jax

    real = jax.config.update

    def guarded(name, value):
        assert name != "jax_compilation_cache_dir", (name, value)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", guarded)


def test_resolve_compile_cache_jax_env_wins(
    tmp_path, monkeypatch, no_config_update
):
    """With JAX_COMPILATION_CACHE_DIR set, the program configures nothing
    and ignores its own knobs (argument and GORDO_COMPILE_CACHE_DIR)."""
    from gordo_components_tpu.utils import resolve_compile_cache

    jax_dir = str(tmp_path / "machine-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", jax_dir)
    monkeypatch.setenv("GORDO_COMPILE_CACHE_DIR", str(tmp_path / "env-knob"))
    assert resolve_compile_cache(str(tmp_path / "cli-knob")) == jax_dir
    assert resolve_compile_cache() == jax_dir
    assert not (tmp_path / "env-knob").exists()
    assert not (tmp_path / "cli-knob").exists()


def test_resolve_compile_cache_knob_second(tmp_path, monkeypatch, cache_config):
    import jax

    from gordo_components_tpu.utils import resolve_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env_knob, cli_knob = str(tmp_path / "env-knob"), str(tmp_path / "cli-knob")
    monkeypatch.setenv("GORDO_COMPILE_CACHE_DIR", env_knob)
    assert resolve_compile_cache() == env_knob
    assert jax.config.jax_compilation_cache_dir == env_knob
    # the explicit argument (--compile-cache-dir) beats the env knob
    assert resolve_compile_cache(cli_knob) == cli_knob
    assert jax.config.jax_compilation_cache_dir == cli_knob


def test_resolve_compile_cache_fixed_default(tmp_path, monkeypatch, cache_config):
    """No env, no knob: <checkout>/.jax_cache — a fixed path (it is part
    of the cache key), never one built from tempfile, a pid or the clock."""
    import tempfile

    import jax

    from gordo_components_tpu.utils import profiling

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("GORDO_COMPILE_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert profiling.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        checkout, ".jax_cache"
    )
    assert not profiling.DEFAULT_COMPILE_CACHE_DIR.startswith(
        tempfile.gettempdir()
    )
    assert str(os.getpid()) not in profiling.DEFAULT_COMPILE_CACHE_DIR
    # same answer on every call (nothing time- or process-derived)...
    monkeypatch.setattr(
        profiling, "DEFAULT_COMPILE_CACHE_DIR", str(tmp_path / ".jax_cache")
    )
    first = profiling.resolve_compile_cache()
    assert first == profiling.resolve_compile_cache() == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_build_app_places_cache_by_env(tmp_path, monkeypatch, no_config_update):
    """build_app goes through the same resolver: with the machine's
    JAX_COMPILATION_CACHE_DIR set it performs no cache-dir update."""
    from gordo_components_tpu.server import build_app

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "machine"))
    # the cache is placed before the collection loads; an empty model dir
    # ends the call right after it
    with pytest.raises(FileNotFoundError):
        build_app(str(tmp_path))
