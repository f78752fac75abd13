"""Conv impl interchangeability: the slice+matmul formulation must be a
numerics- and parameter-exact drop-in for the stock flax conv ops
(models/factories/conv.py), so artifacts/checkpoints move freely between
the two and an A/B comparison is apples-to-apples."""

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from gordo_components_tpu.models.factories.conv import conv1d_autoencoder


@pytest.mark.parametrize("kernel_size", [2, 3, 5])
@pytest.mark.parametrize("lookback", [16, 32])
def test_matmul_impl_matches_lax(kernel_size, lookback):
    x = jnp.asarray(
        np.random.RandomState(0).rand(8, lookback, 6), jnp.float32
    )
    lax_mod = conv1d_autoencoder(6, kernel_size=kernel_size, conv_impl="lax")
    mm_mod = conv1d_autoencoder(6, kernel_size=kernel_size, conv_impl="matmul")
    p = lax_mod.init(jax.random.PRNGKey(0), x)
    # identical parameter tree: either impl loads the other's params
    p2 = mm_mod.init(jax.random.PRNGKey(0), x)
    assert jtu.tree_structure(p) == jtu.tree_structure(p2)
    assert all(
        a.shape == b.shape
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2))
    )
    # identical outputs from the SAME params
    out_lax = lax_mod.apply(p, x)
    out_mm = mm_mod.apply(p, x)
    assert out_lax.shape == out_mm.shape == (8, 6)
    np.testing.assert_allclose(out_lax, out_mm, atol=1e-5)


def test_matmul_impl_matches_lax_bfloat16():
    """bf16 is a production compute dtype, and matmul became
    the DEFAULT impl — so an artifact built under the old lax default that
    reloads under the new one must reconstruct within bf16 resolution, or
    threshold-adjacent anomaly verdicts could silently flip. The two impls
    accumulate in a different order, so exact bitwise equality is not
    guaranteed; the bound here is a couple of bf16 ULPs (bf16 eps ~7.8e-3)
    on outputs of order ~1."""
    x = jnp.asarray(np.random.RandomState(1).rand(8, 32, 6), jnp.float32)
    lax_mod = conv1d_autoencoder(
        6, kernel_size=3, conv_impl="lax", compute_dtype="bfloat16"
    )
    mm_mod = conv1d_autoencoder(
        6, kernel_size=3, conv_impl="matmul", compute_dtype="bfloat16"
    )
    p = lax_mod.init(jax.random.PRNGKey(0), x)
    out_lax = np.asarray(lax_mod.apply(p, x), np.float32)
    out_mm = np.asarray(mm_mod.apply(p, x), np.float32)
    scale = max(1.0, float(np.abs(out_lax).max()))
    np.testing.assert_allclose(out_lax, out_mm, atol=2e-2 * scale)


def test_conv_impl_pinned_across_pickle_and_default_changes():
    """The factory default changed once (lax -> matmul): new artifacts
    must record their impl explicitly, and artifacts pickled BEFORE the
    pin existed must resolve to the old 'lax' default they were trained
    (and threshold-calibrated) under — never to the load-time default."""
    import pickle

    from gordo_components_tpu.models import ConvAutoEncoder

    est = ConvAutoEncoder(channels=(4, 2), epochs=1, lookback_window=8)
    assert est.factory_kwargs["conv_impl"] == "matmul"
    assert est._params["conv_impl"] == "matmul"
    X = np.random.RandomState(0).rand(64, 3).astype(np.float32)
    est.fit(X)
    reloaded = pickle.loads(pickle.dumps(est))
    assert reloaded.factory_kwargs["conv_impl"] == "matmul"
    np.testing.assert_allclose(reloaded.predict(X), est.predict(X))

    # simulate a pre-pin artifact: strip the recorded impl before pickling
    legacy = ConvAutoEncoder(channels=(4, 2), epochs=1, lookback_window=8,
                             conv_impl="lax")
    legacy.fit(X)
    del legacy.factory_kwargs["conv_impl"]
    del legacy._params["conv_impl"]
    revived = pickle.loads(pickle.dumps(legacy))
    assert revived.factory_kwargs["conv_impl"] == "lax"
    assert revived._params["conv_impl"] == "lax"
    assert revived.module.conv_impl == "lax"


def test_bad_conv_impl_rejected():
    x = jnp.zeros((2, 16, 3), jnp.float32)
    mod = conv1d_autoencoder(3, conv_impl="LAX")
    with pytest.raises(ValueError, match="conv_impl"):
        mod.init(jax.random.PRNGKey(0), x)


def test_matmul_impl_trains_in_fleet():
    """conv_impl is a fleetable factory kwarg: a gang configured with it
    trains and its artifacts score."""
    from gordo_components_tpu.builder.fleet_build import extract_fleetable
    from gordo_components_tpu.parallel.fleet import FleetTrainer

    cfg = {
        "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "sklearn.pipeline.Pipeline": {
                    "steps": [
                        "sklearn.preprocessing.MinMaxScaler",
                        {
                            "gordo_components_tpu.models.ConvAutoEncoder": {
                                "lookback_window": 16,
                                "epochs": 1,
                                "conv_impl": "matmul",
                            }
                        },
                    ]
                }
            }
        }
    }
    kw = extract_fleetable(cfg)
    assert kw is not None and kw["conv_impl"] == "matmul"

    rng = np.random.RandomState(0)
    out = FleetTrainer(
        model_type="ConvAutoEncoder", lookback_window=16, epochs=1,
        batch_size=32, conv_impl="matmul",
    ).fit({"m": rng.rand(80, 4).astype("float32")})
    det = out["m"].to_estimator()
    frame = det.anomaly(rng.rand(40, 4).astype("float32"))
    assert np.isfinite(frame[("total-anomaly-scaled", "")].values).all()
