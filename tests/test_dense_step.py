"""The dense gang's training step as one Pallas program (ops/dense_step.py),
in interpret mode on the CPU: one step against ``value_and_grad`` +
``optax.inject_hyperparams(adam)`` + ``apply_updates`` on the same values,
whole fits against the vmapped path, and the rule that says which buckets
take it. What the chip's compiler makes of the kernel is
tests/test_tpu_compile.py's; how fast it is, a chip run's (PERF.md).

Tolerances. Interpret mode computes in float32 like the vmapped CPU path, so
the two differ by the ORDER of float32 sums (the kernel's reductions and
matmuls are its own) and by the update's folded bias corrections: gradients
and both moments agree to a few parts in a million of the leaf's largest
entry. A parameter moves by ``lr * m / (sqrt(v) + eps)``: where a gradient
nearly cancels to zero that ratio turns on the sums' last bits, so
parameters are held to a thousandth of one step (``1e-3 * lr``) plus
float32 rounding of the parameter itself.
"""

import functools
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from gordo_components_tpu.models import train_core
from gordo_components_tpu.models.factories.feedforward import (
    _ACTIVATIONS,
    feedforward_model,
)
from gordo_components_tpu.models.register import lookup_factory
from gordo_components_tpu.ops import dense_step
from gordo_components_tpu.ops.losses import mse_loss
from gordo_components_tpu.parallel import fleet as fleet_mod
from gordo_components_tpu.parallel.fleet import FleetTrainer
from gordo_components_tpu.parallel.mesh import fleet_mesh

F, BATCH, BATCHES = 12, 10, 3


@pytest.fixture
def interpreted(monkeypatch):
    """CPU gangs take the kernel, interpreted, however narrow (this file's
    members have 6 to 12 tags): steered here, in the test, through the
    arguments ``resolve`` has for it; the program has no switch."""
    monkeypatch.setattr(
        dense_step, "resolve",
        functools.partial(dense_step.resolve, modes={"cpu": "interpret"}, narrowest=0),
    )
    fleet_mod._PROGRAM_CACHE.clear()
    yield
    fleet_mod._PROGRAM_CACHE.clear()


def _gang(func, M=4, lr=None):
    """A gang's stacked state as ``vmap(init_fn)`` builds it, the optax
    optimizer it belongs to, and shuffled batches."""
    module = feedforward_model(
        F, encoding_dim=(9, 7), decoding_dim=(7, 9),
        encoding_func=(func, func), decoding_func=(func, func), out_func="linear",
    )
    optimizer = train_core.make_optimizer("adam", 1e-3, inject=True)
    init_fn, _ = train_core.make_train_fns(module, optimizer, BATCH)
    states = jax.vmap(init_fn)(
        jax.random.split(jax.random.PRNGKey(3), M), jnp.zeros((M, F))
    )
    if lr is not None:
        states = fleet_mod._set_stacked_lr(states, lr)
    Xs = jax.random.uniform(jax.random.PRNGKey(1), (M, BATCHES, BATCH, F))
    return module, optimizer, states, Xs


def _reference_step(module, optimizer, params, opt_state, xb, n_real, active):
    """``make_train_fns``'s scan body under the bucket's ``active`` rule,
    for one member."""
    mb = (jnp.arange(BATCH) < n_real).astype(jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda p: mse_loss(module.apply(p, xb), xb, mb)
    )(params)
    updates, new_opt = optimizer.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    keep = (n_real > 0) & (active > 0)
    sel = lambda new, old: jax.tree.map(lambda n, o: jnp.where(keep, n, o), new, old)
    return sel(new_params, params), sel(new_opt, opt_state), loss, grads


def _run_steps(module, optimizer, states, Xs, n_real, active, steps=3):
    """``steps`` steps of the kernel and of the reference from the same
    state: ``((params, opt_state, losses) of each, the last gradients)``."""
    enter, step, leave = dense_step.make_step(module, "interpret")
    hp = states.opt_state.hyperparams
    kernel_step = jax.jit(lambda c, t: step(c, hp, Xs, t, n_real, active))
    reference = jax.jit(jax.vmap(
        lambda p, o, x, n, a: _reference_step(module, optimizer, p, o, x, n, a)
    ))
    carry = enter(states.params, states.opt_state)
    want_p, want_o = states.params, states.opt_state
    for t in range(steps):
        carry, losses = kernel_step(carry, jnp.int32(t % BATCHES))
        want_p, want_o, want_losses, grads = reference(
            want_p, want_o, Xs[:, t % BATCHES], n_real, active
        )
    got_p, got_o = leave(carry, states.opt_state)
    return (got_p, got_o, losses), (want_p, want_o, want_losses), grads


def _assert_state_close(got, want, grads, lr, stepping=slice(None)):
    """``stepping``: the members whose loss is compared (an idle member's
    is 0 from the kernel; the epoch weighs it by no rows, or reports NaN)."""
    (got_p, got_o, got_loss), (want_p, want_o, want_loss) = got, want
    assert jax.tree.structure(got_o) == jax.tree.structure(want_o)
    np.testing.assert_array_equal(got_o.count, want_o.count)
    np.testing.assert_array_equal(
        got_o.inner_state[0].count, want_o.inner_state[0].count
    )
    np.testing.assert_array_equal(
        got_o.hyperparams["learning_rate"], want_o.hyperparams["learning_rate"]
    )
    lr = np.asarray(lr, np.float32)
    flat = lambda tree: jax.tree.leaves_with_path(tree)
    moments = zip(
        flat(got_o.inner_state[0].mu), flat(want_o.inner_state[0].mu),
        flat(got_o.inner_state[0].nu), flat(want_o.inner_state[0].nu),
        flat(got_p), flat(want_p), flat(grads),
    )
    for (path, m), (_, wm), (_, v), (_, wv), (_, p), (_, wp), (_, g) in moments:
        per_member = lambda a: np.max(np.abs(a.reshape(a.shape[0], -1)), axis=1)
        wide = lambda a: a.reshape((-1,) + (1,) * (np.ndim(m) - 1))
        g_max = per_member(np.asarray(g))
        assert np.all(np.abs(m - wm) <= 4e-6 * wide(g_max) + 1e-12), path
        assert np.all(np.abs(v - wv) <= 8e-6 * wide(g_max**2) + 1e-18), path
        assert np.all(
            np.abs(p - wp) <= 1e-3 * wide(lr) + 4e-7 * np.abs(np.asarray(wp))
        ), path
    np.testing.assert_allclose(got_loss[stepping], want_loss[stepping], rtol=2e-6)


@pytest.mark.parametrize("func", sorted(_ACTIVATIONS))
def test_step_matches_optax(func):
    """Three steps (the bias corrections at counts 1 to 3) of four members:
    a full batch, a partly padded one, and two learning rates beside the
    trainer's."""
    lr = np.array([1e-3, 1e-3, 4e-3, 2.5e-4], np.float32)
    module, optimizer, states, Xs = _gang(func, lr=lr)
    n_real = jnp.array([BATCH, 7.0, BATCH, 3.0])
    got, want, grads = _run_steps(
        module, optimizer, states, Xs, n_real, jnp.ones((4,))
    )
    _assert_state_close(got, want, grads, lr)
    np.testing.assert_array_equal(got[1].count, [3, 3, 3, 3])
    # the learning rate is the member's own: same data, another step length
    moved = lambda tree, i: np.abs(
        np.asarray(tree["params"]["Dense_0"]["kernel"][i])
        - np.asarray(states.params["params"]["Dense_0"]["kernel"][i])
    ).max()
    assert moved(got[0], 2) > 2 * moved(got[0], 0)


def _assert_member_bitwise(got_tree, want_tree, i):
    for (path, a), (_, b) in zip(
        jax.tree.leaves_with_path(got_tree), jax.tree.leaves_with_path(want_tree)
    ):
        np.testing.assert_array_equal(np.asarray(a)[i], np.asarray(b)[i], err_msg=str(path))


@pytest.mark.parametrize(
    "n_real,active,idle",
    [
        # a batch of padding alone: nothing changes, count included
        ([0.0, 0.0, 0.0, 0.0], [1.0, 1.0, 1.0, 1.0], [0, 1, 2, 3]),
        # members frozen by early stopping, first, last and in a run
        ([BATCH, BATCH, BATCH, BATCH], [0.0, 1.0, 0.0, 0.0], [0, 2, 3]),
        # both kinds between members that step
        ([BATCH, 0.0, 6.0, BATCH], [1.0, 1.0, 1.0, 0.0], [1, 3]),
    ],
    ids=["all_padding", "inactive", "mixed"],
)
def test_idle_members_keep_their_state_bitwise(n_real, active, idle):
    module, optimizer, states, Xs = _gang("tanh")
    n_real, active = jnp.array(n_real), jnp.array(active)
    got, want, grads = _run_steps(module, optimizer, states, Xs, n_real, active, steps=2)
    for i in idle:
        _assert_member_bitwise((got[0], got[1]), (states.params, states.opt_state), i)
        assert float(got[2][i]) == 0.0
    stepping = np.array([i for i in range(4) if i not in idle], int)
    _assert_state_close(got, want, grads, np.full((4,), 1e-3), stepping)
    np.testing.assert_array_equal(np.asarray(got[1].count)[stepping], 2)


def test_bfloat16_operands_round_like_the_platform_default():
    """On the chip the kernel rounds matmul operands to bfloat16 (what the
    TPU's default precision does to the vmapped path's float32 matmuls) and
    accumulates in float32: against a reference whose every matmul operand
    is rounded the same way, forward and backward."""
    module, optimizer, states, Xs = _gang("tanh", M=2)
    widths, funcs = dense_step.chain_of(module)

    def rounded_apply(params, x):
        h = x
        for l, func in enumerate(funcs):
            layer = params["params"][f"Dense_{l}"]
            h = _ACTIVATIONS[func](
                jnp.dot(
                    h.astype(jnp.bfloat16), layer["kernel"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                ) + layer["bias"]
            )
        return h

    def want_grads(params, xb):
        return jax.grad(lambda p: mse_loss(rounded_apply(p, xb), xb, jnp.ones((BATCH,))))(params)

    enter, _, leave = dense_step.make_step(module, "interpret")
    carry = enter(states.params, states.opt_state)
    # first moments after one step from zero with 1 - b1 = 1 ARE the gradients
    scalars = jnp.zeros((2, 1, 8)).at[:, 0, 0].set(1.0).at[:, 0, 7].set(1.0 / (BATCH * F))
    leaves, _ = dense_step._call(
        funcs, widths, True, jnp.bfloat16, jnp.arange(2, dtype=jnp.int32),
        jnp.full((2,), BATCH, jnp.int32), jnp.zeros((1,), jnp.int32), scalars, Xs, carry[0],
    )
    _, opt_state = leave((leaves, carry[1], carry[2]), states.opt_state)
    want = jax.vmap(want_grads)(states.params, Xs[:, 0])
    for (path, g), (_, w) in zip(
        jax.tree.leaves_with_path(opt_state.inner_state[0].mu),
        jax.tree.leaves_with_path(want),
    ):
        # the backward's own operands (deltas) round where their float32
        # values differ in the last bit: a bfloat16 step of 2^-8 on a term
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-2 * float(np.max(np.abs(w))), err_msg=str(path)
        )


# ------------------------------------------------------------------ #
# which buckets take it
# ------------------------------------------------------------------ #


def _module(registry_type="AutoEncoder", kind="feedforward_hourglass", n=10, **kw):
    return lookup_factory(registry_type, kind)(n, **kw)


# the narrowest default detector the kernel takes (PERF.md section 6, PR 30:
# tools/dense_step_ladder.py on the chip)
NARROWEST_TAGS = 77


@pytest.mark.parametrize(
    "case,why",
    [
        (dict(module=dict(n=300)), None),
        (dict(platform="cpu"), "platform cpu"),
        (dict(opt_name="sgd"), "optimizer sgd"),
        (dict(loss="vae"), "loss vae"),
        (dict(module=dict(compute_dtype="bfloat16")), "compute_dtype bfloat16"),
        (dict(module=dict(registry_type="LSTMAutoEncoder", kind="lstm_hourglass"), seq=(12, 0)),
         "is not a FeedForwardAutoEncoder"),
        (dict(module=dict(registry_type="ConvAutoEncoder", kind="conv1d_autoencoder"), seq=(16, 0)),
         "is not a FeedForwardAutoEncoder"),
        (dict(module=dict(kind="feedforward_variational")), "is not a FeedForwardAutoEncoder"),
        (dict(module=dict(n=4000)), "MiB of VMEM"),
        # examples/fleet.yaml's 10-tag members: 5 kB of state would cross as
        # 168 kB of tiles, and a grid step costs more than their whole step
        (dict(module=dict(n=10)), "member too narrow: 4 KiB of state (168 as tiled)"),
        (dict(module=dict(n=NARROWEST_TAGS - 1)), "member too narrow"),
        (dict(module=dict(n=NARROWEST_TAGS)), None),
    ],
    ids=["default_detector", "cpu", "sgd", "vae_loss", "bfloat16", "lstm", "conv",
         "variational", "too_wide", "fleet_yaml", "under_the_crossover", "at_the_crossover"],
)
def test_resolve_names_the_condition(case, why):
    args = dict(loss="mse", opt_name="adam", seq=None, batch_size=100, platform="tpu")
    args.update({k: v for k, v in case.items() if k != "module"})
    mode, refused = dense_step.resolve(_module(**case.get("module", {})), **args)
    if why is None:
        assert (mode, refused) == ("pallas", None)
    else:
        assert mode is None and why in refused


@pytest.mark.parametrize(
    "widths,want,share",
    [
        # the refit cell's chain: its 250 x 200, 200 x 150 and 250 x 300
        # kernels fill fewer 8 x 128 tiles transposed, 11% of all the bytes
        ((300, 250, 200, 150, 150, 200, 250, 300),
         (False, True, True, False, False, False, True), 0.8883),
        # this file's gang: its 9 x 7 kernel crosses transposed, so every
        # parity case above runs both orientations
        ((F, 9, 7, 7, 9, F), (False, True, False, False, False), 7 / 8),
    ],
    ids=["dense300", "this_file"],
)
def test_kernels_cross_in_the_orientation_that_pads_less(widths, want, share):
    assert dense_step.transposed(widths) == want
    shapes = list(zip(widths, widths[1:]))
    as_stored = sum(
        dense_step._tiled_bytes(*((o, i) if flip else (i, o)))
        for (i, o), flip in zip(shapes, want)
    )
    assert as_stored / sum(dense_step._tiled_bytes(i, o) for i, o in shapes) == pytest.approx(
        share, abs=1e-3
    )


def test_bucket_programs_resolve_the_step_once_per_key(interpreted):
    module = _module()
    one = fleet_mesh(1)
    fused = fleet_mod._bucket_programs(module, "adam", 1e-3, 32, mesh=one)
    assert (fused.layout, fused.fused_step_refused) == ("fused_step", None)
    assert fleet_mod._bucket_programs(module, "adam", 1e-3, 32, mesh=one) is fused
    # the mesh is part of a fused program's key, and of no other's
    assert fleet_mod._bucket_programs(module, "adam", 1e-3, 32, mesh=fleet_mesh(2)) is not fused
    sgd = fleet_mod._bucket_programs(module, "sgd", 1e-3, 32, mesh=one)
    assert (sgd.layout, sgd.fused_step_refused) == ("legacy", "optimizer sgd")
    assert fleet_mod._bucket_programs(module, "sgd", 1e-3, 32, mesh=fleet_mesh(2)) is sgd
    # the epoch program keeps the name the device trace finds it by
    assert fused._vm_epoch.__name__.startswith("masked_epoch")


# ------------------------------------------------------------------ #
# whole fits
# ------------------------------------------------------------------ #


def _members(n=5, rows=140, f=6, seed=0):
    """Ragged members of one bucket: 140, 138, ... rows are five batches of
    32, the fifth partly padding, and the ladder's sixth padding alone."""
    rng = np.random.RandomState(seed)
    out = {}
    for i in range(n):
        t = np.arange(rows - 2 * i)
        base = np.sin(0.1 * (i + 1) * t)[:, None] * np.ones((1, f))
        out[f"m-{i}"] = (base + 0.05 * rng.randn(len(t), f)).astype("float32")
    return out


def _assert_same_models(a, b, rtol, atol):
    assert sorted(a) == sorted(b)
    for name in a:
        np.testing.assert_allclose(
            a[name].history["loss"], b[name].history["loss"], rtol=rtol,
            err_msg=f"{name} loss history",
        )
        for la, lb in zip(jax.tree.leaves(a[name].params), jax.tree.leaves(b[name].params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol)
        np.testing.assert_allclose(
            a[name].feature_thresholds, b[name].feature_thresholds, rtol=10 * rtol
        )
        np.testing.assert_allclose(
            a[name].total_threshold, b[name].total_threshold, rtol=10 * rtol
        )


_FIT = dict(epochs=4, batch_size=32, seed=1)
_HPARAMS = {"m-1": {"learning_rate": 3e-3}}


@pytest.fixture(scope="module")
def vmapped_fit():
    """The default trainer on this CPU: ``vmap(epoch)``."""
    trainer = FleetTrainer(**_FIT, mesh=fleet_mesh(1))
    models = trainer.fit(_members(), member_hparams=_HPARAMS)
    return models, trainer.last_stats


def test_fit_with_the_kernel_matches_the_vmapped_fit(interpreted, vmapped_fit):
    """A small gang (ragged rows, so the last batches are partly and wholly
    padding; one member with its own learning rate) through the whole fit:
    losses, weights, thresholds, and what the stats say of the program."""
    want, want_stats = vmapped_fit
    assert [b["layout"] for b in want_stats["buckets"]] == ["legacy"]
    trainer = FleetTrainer(**_FIT, mesh=fleet_mesh(1))
    got = trainer.fit(_members(), member_hparams=_HPARAMS)
    assert [b["layout"] for b in trainer.last_stats["buckets"]] == ["fused_step"]
    # 20 steps of Adam apart in the order of float32 sums
    _assert_same_models(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("devices", [2, 8])
def test_sharded_gang_steps_under_shard_map(interpreted, vmapped_fit, devices):
    """The gang over the ``models`` axis of a CPU mesh: each device steps
    its own members (5 members pad to 8: dummies train too)."""
    trainer = FleetTrainer(**_FIT, mesh=fleet_mesh(devices))
    got = trainer.fit(_members(), member_hparams=_HPARAMS)
    bucket = trainer.last_stats["buckets"][0]
    assert (bucket["layout"], bucket["device"]["count"]) == ("fused_step", devices)
    _assert_same_models(got, vmapped_fit[0], rtol=2e-4, atol=2e-5)


def test_refused_bucket_says_why_in_its_fit_span(interpreted):
    from gordo_components_tpu.observability.tracing import get_tracer

    def fit_span(**kw):
        FleetTrainer(epochs=1, batch_size=32, mesh=fleet_mesh(1), **kw).fit(_members(2))
        trace = next(t for t in get_tracer().recent() if t.name == "fleet_fit")
        (span,) = [s for s in trace.spans if s.name.startswith("fit:")]
        return span

    took = fit_span()
    assert took.attributes["layout"] == "fused_step"
    assert "fused_step_refused" not in took.attributes
    refused = fit_span(optimizer="sgd")
    assert refused.attributes["layout"] == "legacy"
    assert refused.attributes["fused_step_refused"] == "optimizer sgd"


# ------------------------------------------------------------------ #
# the chip's measuring tool, rehearsed
# ------------------------------------------------------------------ #


def test_ladder_tool_rehearsal(capsys, tmp_path):
    """tools/dense_step_ladder.py at a tiny size, the kernel interpreted:
    both programs of a rung are timed (the times mean nothing here) and a
    ragged gang with frozen members trains the same through both."""
    path = pathlib.Path(__file__).parents[1] / "tools" / "dense_step_ladder.py"
    spec = importlib.util.spec_from_file_location("dense_step_ladder", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "ladder.jsonl"
    rc = tool.main([
        "--interpret", "--rungs", "6x4", "--ragged", "12x16", "--rows", "50",
        "--batch", "8", "--repeats", "1", "--limit", "1e-4", "--out", str(out),
    ])
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    rung, ragged = lines
    assert rc == 0 and (rung["rung"], ragged["ragged"]) == ("6x4", "12x16")
    assert rung["fused_epoch_ms"] > 0 and rung["vmapped_epoch_ms"] > 0
    assert rung["resolve"][1].startswith("member too narrow")
    assert ragged["frozen"] == 5 and ragged["steps_a_member"] == [0, 1, 4, 5, 7]
    assert ragged["frozen_bitwise"] and ragged["counts_right"] and ragged["ok"]
