"""Ask the TPU's compiler, without a TPU (on-chip-measurement guide §2).

libtpu compiles for a chip that is described and not attached: these
tests lower the main path's Pallas kernels and the bank's whole bucket
scoring programs at REAL fleet widths for one v5e chip (and, for the
sharded bank, a mesh of four) and assert the kernel is in the compiled
program. Interpret-mode parity (tests/test_banked_kernel.py,
tests/test_seq_fastpath.py) cannot see what this sees: both batched
kernels passed every interpret test while the chip's lowering refused
them at every member count above one (a size-1 block on a second-last
axis of size M).

A compile that passes is not a chip run: nothing here executes, and no
time or rate comes out of it. ``chip_smoke.py`` is the run.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
# libtpu lets one process at a time load it (a lockfile under /tmp): without
# this, xdist workers that reach this module second cannot describe the
# topology and would skip. Nothing here touches a device.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from gordo_components_tpu.models.register import lookup_factory
from gordo_components_tpu.ops import pallas_score, seq_scan
from gordo_components_tpu.parallel.mesh import MODEL_AXIS
from gordo_components_tpu.server import bank as bank_mod


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host; persistent
    compilation cache off around the module (an entry written for a
    described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_on(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile()


f32, i32 = jnp.float32, jnp.int32


@pytest.mark.parametrize(
    "M,F,T,B",
    [(1, 4, 8, 1), (64, 10, 256, 8), (1024, 10, 256, 64), (10000, 300, 64, 256)],
)
def test_banked_epilogue_compiles_at_fleet_width(v5e, M, F, T, B):
    compiled = _compile_on(
        SingleDeviceSharding(v5e[0]),
        pallas_score._pallas_banked_score,
        ((B, T, F), f32), ((B, T, F), f32), ((M, F), f32), ((M, F), f32),
        ((B,), i32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("R", [8, 4096, 100000])
def test_per_model_epilogue_compiles(v5e, R):
    compiled = _compile_on(
        SingleDeviceSharding(v5e[0]),
        pallas_score._pallas_score,
        ((R, 10), f32), ((R, 10), f32), ((10,), f32), ((10,), f32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "B,M,H",
    [
        (8, 1, 128), (8, 64, 128), (64, 1024, 128), (8, 8, 256),
        # a 8192-row request's windows: the batch axis tiles on the grid
        (8192, 64, 128),
    ],
)
def test_fused_lstm_step_compiles_at_fleet_width(v5e, B, M, H):
    compiled = _compile_on(
        SingleDeviceSharding(v5e[0]),
        seq_scan.fused_lstm_step,
        ((M, B, 4 * H), f32), ((M, B, H), f32), ((M, B, H), f32),
        ((M, H, 4 * H), f32), ((M, 4 * H), f32),
    )
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------------ #
# whole bucket scoring programs (server/bank.py::_Bucket)
# ------------------------------------------------------------------ #

_BUCKETS = {
    # one documented gang of the default detector: 1024 hourglass
    # autoencoders over 10 tags
    "dense": dict(
        registry_type="AutoEncoder", kind="feedforward_hourglass",
        members=1024, lookback=1,
    ),
    # examples/fleet.yaml's sequence member at gang width
    "lstm": dict(
        registry_type="LSTMAutoEncoder", kind="lstm_hourglass",
        members=64, lookback=12,
    ),
}
N_TAGS, ROWS = 10, 256


def _bucket(name, monkeypatch, mesh=None):
    """A finalized ``_Bucket`` of randomly initialised members with the
    device decisions a TPU backend makes (pallas epilogue, time-major
    layout, fused step) — steered here, in the test, because
    ``jax.default_backend()`` is the CPU in this process."""
    spec = _BUCKETS[name]
    monkeypatch.setenv(seq_scan.SEQ_LAYOUT_ENV, "time_major")
    monkeypatch.setenv(seq_scan.SEQ_KERNEL_ENV, "pallas")
    if mesh is not None:
        # a described device cannot hold an array: stand shapes in for
        # the stacked state finalize() places on the mesh
        monkeypatch.setattr(
            jax,
            "device_put",
            lambda tree, sharding=None: jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                tree,
            ),
        )
    module = lookup_factory(spec["registry_type"], spec["kind"])(N_TAGS)
    sample = jnp.zeros(
        (1, N_TAGS) if spec["lookback"] == 1 else (1, spec["lookback"], N_TAGS)
    )
    params = jax.tree.map(
        np.asarray, module.init(jax.random.PRNGKey(0), sample)
    )
    bucket = bank_mod._Bucket(
        spec["kind"], N_TAGS, {}, registry_type=spec["registry_type"],
        lookback=spec["lookback"], mesh=mesh, kernel_mode="pallas",
    )
    vec = np.ones((N_TAGS,), np.float32)
    for i in range(spec["members"]):
        bucket.add(
            bank_mod._BankEntry(
                name=f"m{i}", registry_type=spec["registry_type"],
                kind=spec["kind"], factory_kwargs={}, compute_dtype="float32",
                n_features=N_TAGS, lookback=spec["lookback"], target_offset=0,
                params=params, in_shift=0 * vec, in_scale=vec,
                err_shift=0 * vec, err_scale=vec,
            )
        )
    bucket.finalize()
    return bucket


def _assert_kernels(name, bucket, text):
    assert bucket.kernel_mode == "pallas"
    # the banked epilogue, plus (LSTM) the fused step inside the time scan
    assert "tpu_custom_call" in text
    if name == "lstm":
        assert (bucket.seq_layout, bucket.seq_kernel) == ("time_major", "pallas")
        assert text.count("tpu_custom_call") >= 2
    else:
        assert bucket.seq_layout == "legacy"


@pytest.mark.parametrize("name", ["dense", "lstm"])
def test_bucket_scoring_program_compiles_for_one_chip(v5e, monkeypatch, name):
    B = 64  # one full coalesced batch (BatchingEngine max_batch)
    bucket = _bucket(name, monkeypatch)
    chip = SingleDeviceSharding(v5e[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree
    )
    X = jax.ShapeDtypeStruct((B, ROWS, N_TAGS), f32, sharding=chip)
    idx = jax.ShapeDtypeStruct((B,), i32, sharding=chip)
    compiled = bucket._score.lower(
        on_chip(bucket.params), *on_chip(bucket.scalers), idx, X, X
    ).compile()
    _assert_kernels(name, bucket, compiled.as_text())
    mem = compiled.memory_analysis()
    resident = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert resident < 16e9  # one v5e chip's HBM


@pytest.mark.parametrize("name", ["dense", "lstm"])
def test_sharded_bucket_program_compiles_for_four_chips(v5e, monkeypatch, name):
    """The bank sharded over a four-chip ``models`` mesh
    (GORDO_SERVER_DEVICES=4): a ``pallas_call`` inside ``shard_map``,
    each chip scoring its own sub-batch against its quarter of the
    stack — and no collective in the program."""
    mesh = Mesh(np.asarray(v5e), (MODEL_AXIS,))
    bucket = _bucket(name, monkeypatch, mesh=mesh)
    sharded = NamedSharding(mesh, P(MODEL_AXIS))
    B = 8  # slots per shard
    X = jax.ShapeDtypeStruct((4, B, ROWS, N_TAGS), f32, sharding=sharded)
    idx = jax.ShapeDtypeStruct((4, B), i32, sharding=sharded)
    assert bucket.shard_size == _BUCKETS[name]["members"] // 4
    compiled = bucket._score.lower(
        bucket.params, *bucket.scalers, idx, X, X
    ).compile()
    text = compiled.as_text()
    _assert_kernels(name, bucket, text)
    for collective in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert collective not in text, collective
