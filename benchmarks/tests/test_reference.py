"""The plain reference against the program on the CPU at a small size, and
the benchmark's wire code against the program's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
from harness import adapter, reference, weights, wire
from queued import cell as make_cell


def _tiny(name):
    cell = make_cell(name, overrides={"config": {"tags_per_machine": 12}})
    return cell.config


def test_reference_init_is_the_trainers_init_bitwise():
    from gordo_components_tpu.models.register import lookup_factory

    module = lookup_factory("AutoEncoder", "feedforward_hourglass")(30)
    rng = jax.random.PRNGKey(2**31 - 5)
    theirs = module.init(rng, jnp.zeros((1, 30)))["params"]
    ours = families.load("dense", "refit").dense_init(rng, (30,) + weights.hourglass_dims(30, 3, 0.5) + (30,))
    for k in range(7):
        np.testing.assert_array_equal(theirs[f"Dense_{k}"]["kernel"], ours[f"w{k}"])
        np.testing.assert_array_equal(theirs[f"Dense_{k}"]["bias"], ours[f"b{k}"])


@pytest.mark.parametrize("name", ["dense300.live", "lstm300.backfill"])
def test_reference_scores_like_the_per_model_path(name):
    config = _tiny(name)
    det = adapter.make_member(config, 5, 2)
    X = weights.request_body(config, 5, 0, 40)
    frame = det.anomaly(X)
    want = reference.anomaly(config, weights.member_weights(config, 5, 2), X)
    for key in ("model-output", "tag-anomaly-unscaled", "tag-anomaly-scaled"):
        np.testing.assert_allclose(frame[key].to_numpy(), want[key], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        frame[("total-anomaly-scaled", "")].to_numpy(), want["total-anomaly-scaled"], rtol=2e-5
    )
    np.testing.assert_array_equal(frame["model-input"].to_numpy(), want["model-input"])


def test_wire_matches_the_programs_format():
    from gordo_components_tpu.utils import wire as theirs

    X = np.arange(24, dtype=np.float32).reshape(6, 4)
    assert wire.pack([("X", X)]) == theirs.pack_frames([("X", X)])
    body = theirs.pack_frames([("a", X), ("b", np.float32(3.0).reshape(())), ("c", X[:, 0])])
    got = wire.unpack(body)
    assert list(got) == ["a", "b", "c"]
    np.testing.assert_array_equal(got["a"], X)
    assert wire.ANOMALY_FRAMES == theirs.ANOMALY_FRAME_NAMES
    assert wire.CONTENT_TYPE == theirs.TENSOR_CONTENT_TYPE
    with pytest.raises(ValueError):
        wire.unpack(body[:-1])
