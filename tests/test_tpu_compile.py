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

import re

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


def _bucket(name, monkeypatch, home, n_tags=N_TAGS, stand_in=None):
    """A finalized ``_Bucket`` of randomly initialised members with the
    device decisions a TPU backend makes (pallas epilogue, time-major
    layout, fused step) — steered here, in the test, because
    ``jax.default_backend()`` is the CPU in this process.

    A described device cannot hold an array: around ``finalize()``,
    shapes stand in for the stacked state it places, on ``home`` (the
    described chip, or a ``NamedSharding`` on the described mesh).
    ``stand_in`` members instead of the few really stacked: the bank's
    leading dimension at a real size, for nothing."""
    spec = _BUCKETS[name]
    monkeypatch.setenv(seq_scan.SEQ_LAYOUT_ENV, "time_major")
    monkeypatch.setenv(seq_scan.SEQ_KERNEL_ENV, "pallas")
    members = spec["members"] if stand_in is None else 4

    def describe(a, sharding=None):
        assert sharding in (None, home)
        shape = (stand_in or a.shape[0],) + a.shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=home)

    store = bank_mod._store_members

    def stored(a, sharding=None):
        # ``finalize`` lays each placed leaf out again on the device:
        # here its shape does
        return jax.ShapeDtypeStruct(jax.eval_shape(store, a).shape, a.dtype, sharding=home)

    mesh = home.mesh if isinstance(home, NamedSharding) else None
    module = lookup_factory(spec["registry_type"], spec["kind"])(n_tags)
    sample = jnp.zeros(
        (1, n_tags) if spec["lookback"] == 1 else (1, spec["lookback"], n_tags)
    )
    params = jax.tree.map(
        np.asarray, module.init(jax.random.PRNGKey(0), sample)
    )
    bucket = bank_mod._Bucket(
        spec["kind"], n_tags, {}, registry_type=spec["registry_type"],
        lookback=spec["lookback"], mesh=mesh, kernel_mode="pallas",
    )
    vec = np.ones((n_tags,), np.float32)
    for i in range(members):
        bucket.add(
            bank_mod._BankEntry(
                name=f"m{i}", registry_type=spec["registry_type"],
                kind=spec["kind"], factory_kwargs={}, compute_dtype="float32",
                n_features=n_tags, lookback=spec["lookback"], target_offset=0,
                params=params, in_shift=0 * vec, in_scale=vec,
                err_shift=0 * vec, err_scale=vec,
            )
        )
    with monkeypatch.context() as described:
        described.setattr(jax, "device_put", describe)
        described.setattr(bank_mod, "_store_members", stored)
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


def _home(v5e, chips):
    """Where a bank lives: one described chip, or the ``models`` mesh
    over all four (GORDO_SERVER_DEVICES=4)."""
    if chips == 1:
        return SingleDeviceSharding(v5e[0])
    return NamedSharding(Mesh(np.asarray(v5e), (MODEL_AXIS,)), P(MODEL_AXIS))


def _compile_bucket(bucket, home, B, n_tags=N_TAGS):
    """The bucket program for B slots (per shard, under a mesh)."""
    lead = (B,) if bucket.mesh is None else (bucket.n_shards, B)
    X = jax.ShapeDtypeStruct(lead + (ROWS, n_tags), f32, sharding=home)
    idx = jax.ShapeDtypeStruct(lead, i32, sharding=home)
    return bucket._score.lower(
        bucket.params, *bucket.scalers, idx, X, X
    ).compile()


@pytest.mark.parametrize("name", ["dense", "lstm"])
def test_bucket_scoring_program_compiles_for_one_chip(v5e, monkeypatch, name):
    B = 64  # one full coalesced batch (BatchingEngine max_batch)
    chip = _home(v5e, 1)
    bucket = _bucket(name, monkeypatch, chip)
    compiled = _compile_bucket(bucket, chip, B)
    _assert_kernels(name, bucket, compiled.as_text())
    mem = compiled.memory_analysis()
    resident = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert resident < 16e9  # one v5e chip's HBM


def _no_collectives(text):
    for collective in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert collective not in text, collective


@pytest.mark.parametrize("name", ["dense", "lstm"])
def test_sharded_bucket_program_compiles_for_four_chips(v5e, monkeypatch, name):
    """The bank sharded over a four-chip ``models`` mesh
    (GORDO_SERVER_DEVICES=4): a ``pallas_call`` inside ``shard_map``,
    each chip scoring its own sub-batch against its quarter of the
    stack — and no collective in the program."""
    sharded = _home(v5e, 4)
    bucket = _bucket(name, monkeypatch, sharded)
    assert bucket.shard_size == _BUCKETS[name]["members"] // 4
    text = _compile_bucket(bucket, sharded, 8).as_text()  # 8 slots per shard
    _assert_kernels(name, bucket, text)
    _no_collectives(text)


# ------------------------------------------------------------------ #
# the bucket program reads the members it scores, not the bank
# ------------------------------------------------------------------ #

# (bucket, tags, members stood in as shapes, chips). ``dense300`` is the
# benchmark's live cell: 4096 members of 1.34 MB, 5.5 GB of stack.
# Member counts no batch size, layer width or row count equals, so a
# shape that leads with one IS the bank's.
_BANKS = {
    "dense300": ("dense", 300, 4096, 1),
    "lstm": ("lstm", N_TAGS, 640, 1),
    "dense-sharded": ("dense", N_TAGS, 4096, 4),
    "lstm-sharded": ("lstm", N_TAGS, 640, 4),
}
# XLA's byte count of the dense300 program (``cost_analysis()``), an upper
# limit per B: the parent read 9.2 GB at B = 2 and 10.1 GB at B = 64, at
# 819 GB/s the 14 ms a batch the chip measured
_BYTES_LIMIT = {2: 0.3e9, 64: 2e9}

_LAYOUT = re.compile(r"\{[^{}]*\}")
# ``%name = <result type> opcode(``, layouts removed: a tuple result has
# no parenthesis of its own left
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = (\([^()]*\)|\S+) [\w\-]+\(")


def _bank_sized(text, members):
    """Instructions of the scheduled module with a result that leads with
    the bank's member count. Parameters are left out: the entry's are the
    bank, a fused computation's are views of its operands."""
    lead = re.compile(r"\w+\[%d[,\]]" % members)
    found = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(_LAYOUT.sub("", line))
        if m and " parameter(" not in line and lead.search(m.group(1)):
            found.append(line.strip()[:240])
    return found


def _is_prefetch(line):
    """XLA moving one entry parameter into the chip's fast memory (``S(1)``)
    ahead of its use, in the layout it has: no relayout, no arithmetic, and
    only ever of a leaf that fits there whole (a scaler stack of the live
    cell at B = 1: 4.9 MB; the 1.2 GB kernels never)."""
    m = re.search(r"= \((\S+), (\S+), u32\[\]\S*\) copy-start\(", line)
    if m:
        return m.group(1).replace("S(1)", "") == m.group(2)
    m = re.search(r"= (\S+) copy-done\(", line)
    return bool(m and re.search(r"\{(2,1,0|1,0|0):\S*S\(1\)\}$", m.group(1)))


@pytest.mark.parametrize("B", [1, 2, 8, 64])
@pytest.mark.parametrize("bank", sorted(_BANKS))
def test_bucket_program_reads_only_the_members_it_scores(v5e, monkeypatch, bank, B):
    """ISSUE 27. The stack lies member-major and the program slices its B
    members out before it computes, so (a) nothing but an entry parameter
    has the bank's leading dimension — XLA used to lay the WHOLE bank out
    again (and round it to bf16 on the way) for the matmul behind a gather,
    on every dispatch of two or more requests; (b) the compiler's own byte
    count is that of B members; (c) every stacked leaf enters member-major
    in the layout the device gives it unasked, so nothing is laid out again
    on entry."""
    name, n_tags, members, chips = _BANKS[bank]
    home = _home(v5e, chips)
    bucket = _bucket(name, monkeypatch, home, n_tags=n_tags, stand_in=members)
    compiled = _compile_bucket(bucket, home, B, n_tags=n_tags)
    text = compiled.as_text()
    _assert_kernels(name, bucket, text)
    _no_collectives(text)
    # (c) every stacked leaf enters member-major, in the default layout of
    # its stored shape: what an array placed with no layout asked has
    stacked = jax.tree.leaves((bucket.params, bucket.scalers))
    entry = jax.tree.leaves(compiled.input_formats[0][:5])  # all but idx, X, Y
    assert len(entry) == len(stacked)
    for leaf, fmt in zip(stacked, entry):
        assert leaf.shape[0] == members and leaf.format.layout is None
        assert fmt.layout.major_to_minor[0] == 0, (leaf, fmt)
    # (a) in the program a shard's block of the stack is the bank
    local = members // chips
    work = _bank_sized(text, local)
    assert [l for l in work if not _is_prefetch(l)] == []
    if bank == "dense300":
        # (b), and what member-major costs: whole tiles per member, kept
        # under 15% here by putting the better of a kernel's two
        # dimensions on the lanes (as the model has them: 27%)
        if B in _BYTES_LIMIT:
            assert compiled.cost_analysis()["bytes accessed"] < _BYTES_LIMIT[B]
        assert compiled.memory_analysis().argument_size_in_bytes < 1.15 * (
            members * bucket.params_per_member * 4
        )


# --------------------------------------------------------------------- #
# a bucket with shared leaves: the trunk's layer program
# --------------------------------------------------------------------- #

_ITEM_BYTES = {"f32": 4, "s32": 4, "bf16": 2, "s8": 1, "u8": 1, "pred": 1}
_COPY = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* copy\(%([\w.]+)\)")


def _copies(text, floor=16e6):
    """``(result, operand)`` of every ``copy`` in a compiled program, the
    fusions' bodies included, that writes ``floor`` bytes or more: what
    layout assignment moves whole, doing no arithmetic."""
    found = []
    for line in text.splitlines():
        m = _COPY.match(line)
        if m and np.prod([int(d) for d in m.group(2).split(",") if d]) * _ITEM_BYTES[m.group(1)] >= floor:
            found.append((f"{m.group(1)}[{m.group(2)}]", m.group(3)))
    return found


def _routed_copies(text):
    """A routed layer's large copies, split: those of an expert stack
    entering the program (the ``w['up']``, ``w['gate']``, ``w['down']``
    parameters), and the float32 ones (the combine's block and output)."""
    copies = _copies(text)
    stacks = [c for c in copies if re.match(r"w__(up|gate|down)__", c[1])]
    return stacks, sorted(shape for shape, _ in copies if shape.startswith("f32"))


@pytest.mark.parametrize("B", [1, 2])
def test_trunk_layer_program_compiles_at_published_widths(v5e, B):
    """One decoder layer of the shared trunk (128 experts of 768, top 8;
    32/4 heads of 128; the indexer's top 2048) over B week-long requests
    of 10 240 padded rows, for one chip: the three grouped matmuls and the
    masked attention are Pallas kernels, and what the program needs beside
    its arguments stays under the count the bank bounds its batch by."""
    from gordo_components_tpu.models.factories.trunk import SparseMoEDecoder

    module = SparseMoEDecoder(n_features=300, num_hidden_layers=6)
    home = SingleDeviceSharding(v5e[0])
    T = module.padded_rows(10080)
    assert T == 10240
    layer = {
        name: jax.ShapeDtypeStruct(
            shape, jnp.float32 if len(shape) == 1 else jnp.bfloat16, sharding=home
        )
        for name, shape in module.layer_shapes().items()
    }
    x = jax.ShapeDtypeStruct((B, T, module.hidden_size), jnp.float32, sharding=home)
    n_valid = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=home)
    compiled = jax.jit(
        lambda w, x, n: module.layer(w, x, n, None, interpret=False)
    ).lower(layer, x, n_valid).compile()
    assert compiled.as_text().count("tpu_custom_call") == 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= module.program_bytes(B, T), (temp, module.program_bytes(B, T))
    # and the count is not so loose that a chip's worth of batch is refused
    assert module.program_bytes(B, T) <= 1.6 * temp
    weights = sum(np.prod(s.shape) * s.dtype.itemsize for s in layer.values())
    assert 1.24e9 < weights < 1.26e9  # 625.4 M parameters a layer in bfloat16


@pytest.mark.parametrize("kind,layer_index,B,kernels,gigabytes", [
    ("dense", 0, 1, 1, (0.99, 1.00)), ("routed", 1, 1, 4, (1.34, 1.36)),
    ("routed", 1, 2, 4, (1.34, 1.36)),
])
def test_latent_trunk_layer_programs_compile_at_published_widths(
        v5e, kind, layer_index, B, kernels, gigabytes):
    """Both kinds of layer of the latent-attention trunk (64 heads of
    128 + 64 | 128 over ranks 1536 and 512; a dense SwiGLU of 18 432; 12 of
    192 experts of 2048 beside a shared one, top 8 of 4 of 8 groups) over
    B week-long requests of 10 240 padded rows, for one chip: the latent
    attention, and in the routed layer the three grouped matmuls, are
    Pallas kernels, and what each program needs beside its arguments stays
    under the count the bank bounds its batch by."""
    from gordo_components_tpu.models.factories.trunk import LatentMoEDecoder

    module = LatentMoEDecoder(
        n_features=300, num_hidden_layers=6, experts_held=12,
        rope_scaling=dict(type="yarn", factor=32, original_max_position_embeddings=4096,
                          beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    )
    home = SingleDeviceSharding(v5e[0])
    T = module.padded_rows(10080)
    layer = {
        name: jax.ShapeDtypeStruct(
            shape, jnp.float32 if len(shape) == 1 else jnp.bfloat16, sharding=home
        )
        for name, shape in module.layer_shapes(layer_index).items()
    }
    assert ("router" in layer) == (kind == "routed")
    x = jax.ShapeDtypeStruct((B, T, module.hidden_size), jnp.float32, sharding=home)
    n_valid = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=home)
    compiled = jax.jit(
        lambda w, x, n: module.layer(w, x, n, None, interpret=False)
    ).lower(layer, x, n_valid).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    if kind == "routed":
        # D 7168 is 56 rows of 128, whole (8, 128) tiles: the combine adds into (tokens, 56, 128)
        # unpadded, and its one relayout is a trip's block into a token's tiles; no stack is copied
        assert _routed_copies(compiled.as_text()) == ([], ["f32[256,8,56,128]"])
    temp = compiled.memory_analysis().temp_size_in_bytes
    before = {("dense", 1): 1.17, ("routed", 1): 2.07, ("routed", 2): 2.52}[kind, B]
    print(f"{kind} layer, {B} request(s): temp {temp / 1e9:.2f} GB ({before} GB before the held pairs "
          f"went in blocks), the bank's count {module.program_bytes(B, T) / 1e9:.2f} GB")
    assert temp <= module.program_bytes(B, T), (temp, module.program_bytes(B, T))
    if kind == "routed" and B == 1:
        # 2.07 GB while a run's passes were sized by all its 20 480 pairs; in blocks of
        # held pairs the run's point is a block's, and the widest is the attention's. The
        # count is as it was (it decides the batch: one request a call either way)
        assert temp < 1.2e9 and module.program_bytes(B, T) <= 3.2 * temp
    # beside the 7.75 GB trunk and the 2.5 GB bank a chip of 16.9e9 bytes takes two
    # requests a call by this count, not four
    free = 16.9e9 - 7.75e9 - 2.5e9
    assert module.program_bytes(2, T) < free < module.program_bytes(4, T)
    weights = sum(np.prod(s.shape) * s.dtype.itemsize for s in layer.values())
    assert gigabytes[0] * 1e9 < weights < gigabytes[1] * 1e9


@pytest.mark.parametrize("kind,layer_index,handed,kernels,gigabytes", [
    ("dense+full", 0, False, 1, (0.80, 0.81)), ("routed+shared", 1, True, 4, (1.61, 1.63)),
    ("routed+full", 4, True, 4, (1.63, 1.65)),
])
def test_selected_latent_trunk_layer_programs_compile_at_published_widths(
        v5e, kind, layer_index, handed, kernels, gigabytes):
    """The three kinds of layer of the latent-attention trunk under a
    shared selection (``glm52_trunk300``: 64 heads of 192 + 64 | 256 over
    ranks 2048 and 512; an indexer of 32 x 128 that keeps 2048 keys in the
    ``full`` layers; a dense SwiGLU of 12 288; 16 of 256 experts of 2048
    beside a shared one, top 8 under a correction bias) over one week-long
    request of 10 240 padded rows, for one chip: the latent attention
    under the (rows, rows) int8 selection, and in a routed layer the three
    grouped matmuls, are Pallas kernels; every kind hands the selection on
    (105 MB beside the residual stream); and what each program needs
    beside its arguments stays under the count the bank bounds its batch
    by, which reads 2.3-2.8 times the compiler's analysis here (its points
    are summed as if they coincided): one request a call beside the 7.29 GB
    trunk and the bank, not two."""
    import json

    from gordo_components_tpu.models.factories.trunk import LatentMoEDecoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", "glm52_trunk300.json")) as fh:
        sizes = json.load(fh)["model"]["gordo_components_tpu.models.DiffBasedAnomalyDetector"][
            "base_estimator"]["sklearn.pipeline.Pipeline"]["steps"][-1][
            "gordo_components_tpu.models.TrunkForecast"]
    sizes = {k: v for k, v in sizes.items() if k not in ("kind", "trunk")}
    module = LatentMoEDecoder(n_features=300, **dict(sizes, indexer_types=tuple(sizes["indexer_types"])))
    home = SingleDeviceSharding(v5e[0])
    B, T = 1, module.padded_rows(10080)
    layer = {
        name: jax.ShapeDtypeStruct(
            shape, jnp.float32 if len(shape) == 1 else jnp.bfloat16, sharding=home
        )
        for name, shape in module.layer_shapes(layer_index).items()
    }
    assert ("router" in layer, "idx_wq" in layer) == ("routed" in kind, "full" in kind)
    x = jax.ShapeDtypeStruct((B, T, module.hidden_size), jnp.float32, sharding=home)
    n_valid = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=home)
    selection = jax.ShapeDtypeStruct((B, T, T), jnp.int8, sharding=home) if handed else None
    compiled = jax.jit(
        lambda w, x, n, s: module.layer(w, x, n, s, interpret=False)
    ).lower(layer, x, n_valid, selection).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    if "routed" in kind:  # D 6144, 48 rows of 128: as the other latent trunk's
        assert _routed_copies(compiled.as_text()) == ([], ["f32[256,8,48,128]"])
    memory = compiled.memory_analysis()
    temp = memory.temp_size_in_bytes
    before = {"dense+full": 1.96, "routed+shared": 1.85, "routed+full": 2.08}[kind]
    print(f"{kind} layer: temp {temp / 1e9:.2f} GB ({before} GB before the held pairs went in blocks: "
          f"the attention under its selection is the widest point either way), "
          f"out {memory.output_size_in_bytes / 1e9:.2f} GB, "
          f"the bank's count {module.program_bytes(B, T) / 1e9:.2f} GB")
    assert memory.output_size_in_bytes >= B * T * (4 * module.hidden_size + T)  # x and the selection
    assert temp <= module.program_bytes(B, T) <= 2.8 * temp, (temp, module.program_bytes(B, T))
    free = 16.9e9 - 7.29e9 - 2.2e9 - 0.8e9  # the trunk, the bank as stored, what else the server holds
    assert module.program_bytes(1, T) < free < module.program_bytes(2, T)
    weights = sum(np.prod(s.shape) * s.dtype.itemsize for s in layer.values())
    assert gigabytes[0] * 1e9 < weights < gigabytes[1] * 1e9


@pytest.mark.parametrize("kind,layer_index,B,kernels,gigabytes", [
    ("M", 0, 1, 1, (0.077, 0.078)), ("M", 0, 2, 1, (0.077, 0.078)), ("E", 1, 1, 2, (1.317, 1.319)),
    ("*", 5, 1, 1, (0.046, 0.047)),
])
def test_hybrid_trunk_layer_programs_compile_at_published_widths(
        v5e, kind, layer_index, B, kernels, gigabytes):
    """The three kinds of layer of the hybrid trunk (``nemotron3_trunk300``:
    a Mamba-2 mixer of 64 heads of 64 over 8 groups of 128, scanned in
    chunks of 128; 64 of 128 squared-ReLU experts of 1856 beside a shared
    one of 3712, top 6; attention of 32/2 heads of 128 over every causal
    key) over B week-long requests of 10 240 padded rows, for one chip: the
    scan, the two grouped matmuls and the mask-free attention are Pallas
    kernels, and what each program needs beside its arguments stays under
    the count the bank bounds its batch by, the mixer's (the widest)
    within twice the compiler's analysis.

    The routed layer relays out no expert stack and nothing at its width
    of 2688 = 21 rows of 128, which is not whole (8, 128) tiles: the up
    stack (64, 2688, 1856), whose 1856 columns are not whole lanes, lies
    in HBM with its rows on the lanes and is read so (it was copied whole
    every call, 638 MB), and the combine adds into (tokens, 24, 128) (its
    block and its output were relaid out twice a trip and twice a call at
    21). What is left is what the other trunks' combine has: a trip's
    block into a token's tiles, and the output into the residual's."""
    from gordo_components_tpu.models.factories.trunk import HybridMoEDecoder

    module = HybridMoEDecoder(n_features=300, num_hidden_layers=9, experts_held=64)
    home = SingleDeviceSharding(v5e[0])
    T = module.padded_rows(10080)
    assert (T, module.kind(layer_index)) == (10240, kind)
    layer = {
        name: jax.ShapeDtypeStruct(
            shape, jnp.float32 if len(shape) == 1 else jnp.bfloat16, sharding=home
        )
        for name, shape in module.layer_shapes(layer_index).items()
    }
    x = jax.ShapeDtypeStruct((B, T, module.hidden_size), jnp.float32, sharding=home)
    n_valid = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=home)
    compiled = jax.jit(
        lambda w, x, n: module.layer(w, x, n, None, interpret=False)
    ).lower(layer, x, n_valid).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"{kind} layer, {B} request(s): temp {temp / 1e9:.2f} GB, "
          f"the bank's count {module.program_bytes(B, T) / 1e9:.2f} GB")
    assert temp <= module.program_bytes(B, T), (temp, module.program_bytes(B, T))
    if kind == "M":
        assert module.program_bytes(B, T) <= 2 * temp
    if kind == "E":
        assert _routed_copies(compiled.as_text()) == ([], ["f32[1280,8,24,128]", "f32[256,8,24,128]"])
        assert temp < 0.4e9  # 0.83 GB while the up stack was copied, the copy among the temporaries
    weights = sum(np.prod(s.shape) * s.dtype.itemsize for s in layer.values())
    assert gigabytes[0] * 1e9 < weights < gigabytes[1] * 1e9


# --------------------------------------------------------------------- #
# the dense gang's training step (ops/dense_step.py) in its epoch program
# --------------------------------------------------------------------- #

# benchmarks/configs/dense300.json: the default detector on a 300-tag
# machine, chain 300-250-200-150-150-200-250-300, batch 100, 1440 rows
# padded to 16 batches
_REFIT = dict(tags=300, batch=100, rows=1600, local_members=640)


def _epoch_program(v5e, chips, n_tags=_REFIT["tags"], members=None, **module_kw):
    """``(programs, compiled epoch program)`` of a dense bucket whose gang
    sits on ``chips`` described chips: ``_bucket_programs`` resolves the
    step from the mesh's own devices, as ``FleetTrainer._fit_bucket`` asks
    it to; shapes stand in for the stacked state."""
    from gordo_components_tpu.parallel import fleet

    mesh = Mesh(np.asarray(v5e[:chips]), (MODEL_AXIS,))
    home = NamedSharding(mesh, P(MODEL_AXIS))
    module = lookup_factory("AutoEncoder", "feedforward_hourglass")(
        n_tags, compute_dtype="float32", **module_kw
    )
    progs = fleet._bucket_programs(
        module, "adam", 1e-3, _REFIT["batch"], None, "mse", 1.0, 1.0, mesh=mesh
    )
    M = members or _REFIT["local_members"] * chips
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=home), tree
    )
    rngs = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), M))
    states = jax.eval_shape(
        progs.init_stacked, rngs, jax.ShapeDtypeStruct((M, n_tags), f32)
    )
    compiled = progs.run_epoch.lower(
        on(states),
        jax.ShapeDtypeStruct((M, _REFIT["rows"], n_tags), f32, sharding=home),
        jax.ShapeDtypeStruct((M, _REFIT["rows"]), f32, sharding=home),
        jax.ShapeDtypeStruct((M,), f32, sharding=home),
    ).compile()
    fleet._PROGRAM_CACHE.clear()  # a program for described devices serves no fit
    return progs, compiled


def _while_bodies(text):
    """The scheduled module's ``while`` body computations, by name."""
    names = set(re.findall(r"\bbody=(%[\w.\-]+)", text))
    blocks = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
    return {b.split(" ", 1)[0]: b for b in blocks if b.split(" ", 1)[0] in names}


@pytest.mark.parametrize("chips", [1, 4])
def test_dense_step_compiles_inside_the_epoch_program(v5e, chips):
    """ISSUE 30, at the refit cell's real shapes (640 members a chip): the
    epoch program of a default-detector gang is a scan whose body is the one
    custom call. No state leaf is copied or laid out again inside the scan
    (a ``[640, ...]`` copy there would stream 2.6 GB a step); at the
    program's two ends they are, once an epoch (the device's default layout
    of a stacked leaf is member-minor, the kernel's blocks are a member's).
    Over four chips the gang steps under ``shard_map``: no collective."""
    progs, compiled = _epoch_program(v5e, chips)
    assert (progs.layout, progs.fused_step_refused) == ("fused_step", None)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    _no_collectives(text)
    bodies = _while_bodies(text)
    assert any("tpu_custom_call" in body for body in bodies.values())
    for body in bodies.values():
        # beside the kernel (and the tuples around it) a step computes only
        # per-member scalars: counts, block indices, eight floats a member,
        # its loss. A state leaf there, copied or computed, is a pass over it
        for line in body.splitlines():
            m = _INSTRUCTION.match(_LAYOUT.sub("", line))
            if not m or re.search(r" (parameter|get-tuple-element|tuple|bitcast)\(", line):
                continue
            if "%dense_train_step" in line.split(" = ")[0]:
                continue
            for dims in re.findall(r"\w+\[%d,([\d,]+)\]" % _REFIT["local_members"], m.group(1)):
                assert np.prod([int(d) for d in dims.split(",")]) <= 128, line.strip()[:240]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9  # of 16 GB


@pytest.mark.parametrize("func", ["relu", "sigmoid", "elu", "softplus", "linear"])
def test_dense_step_lowers_every_activation(v5e, func):
    """What interpret mode cannot say: that the chip's kernel compiler has
    each activation and its derivative (it has no ``expm1``: ops/dense_step
    writes ``elu`` without it)."""
    progs, compiled = _epoch_program(v5e, 1, members=8, func=func)
    assert progs.layout == "fused_step"
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize(
    "tags,why", [(4000, "MiB of VMEM"), (10, "member too narrow")], ids=["too_wide", "too_narrow"]
)
def test_member_the_kernel_does_not_pay_for_keeps_the_vmapped_epoch(v5e, tags, why):
    """A 4000-tag hourglass is 59 M parameters a member: four blocks of its
    state do not fit the kernel's VMEM budget. A 10-tag one (upstream's
    examples, ``examples/fleet.yaml``) is 5 kB that would cross as 168 kB of
    tiles, under a grid step that costs more than its whole vmapped step.
    Either bucket resolves to ``vmap(epoch)`` and says why, instead of
    failing in the compiler or training slower."""
    from gordo_components_tpu.parallel import fleet

    mesh = Mesh(np.asarray(v5e[:1]), (MODEL_AXIS,))
    module = lookup_factory("AutoEncoder", "feedforward_hourglass")(tags)
    progs = fleet._bucket_programs(module, "adam", 1e-3, 100, mesh=mesh)
    fleet._PROGRAM_CACHE.clear()
    assert progs.layout == "legacy"
    assert why in progs.fused_step_refused
