"""Fleet-build bridge tests: fleetable-config detection and the gang build
path end-to-end on RandomDataset data."""

import os

import pytest

from gordo_components_tpu import serializer
from gordo_components_tpu.builder.fleet_build import build_fleet, extract_fleetable
from gordo_components_tpu.workflow.config import DEFAULT_MODEL_CONFIG, Machine

DATASET = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00Z",
    "train_end_date": "2020-01-01T12:00:00Z",
    "tag_list": ["a", "b", "c"],
}

FLEETABLE = {
    "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "sklearn.pipeline.Pipeline": {
                "steps": [
                    "sklearn.preprocessing.MinMaxScaler",
                    {
                        "gordo_components_tpu.models.AutoEncoder": {
                            "kind": "feedforward_symmetric",
                            "dims": [8],
                            "epochs": 2,
                            "batch_size": 64,
                        }
                    },
                ]
            }
        }
    }
}


class TestExtractFleetable:
    def test_default_config_is_fleetable(self):
        kwargs = extract_fleetable(DEFAULT_MODEL_CONFIG)
        assert kwargs == {"kind": "feedforward_hourglass"}

    def test_custom_kwargs_extracted(self):
        kwargs = extract_fleetable(FLEETABLE)
        assert kwargs["kind"] == "feedforward_symmetric"
        assert kwargs["epochs"] == 2

    def test_standard_scaler_fleetable(self):
        for path in (
            "sklearn.preprocessing.StandardScaler",
            "gordo_components_tpu.models.transformers.JaxStandardScaler",
        ):
            config = {
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                path,
                                {
                                    "gordo_components_tpu.models.AutoEncoder": {
                                        "epochs": 2, "batch_size": 64,
                                    }
                                },
                            ]
                        }
                    }
                }
            }
            kwargs = extract_fleetable(config)
            assert kwargs is not None and kwargs["input_scaler"] == "standard"

    def test_user_supplied_input_scaler_kwarg_not_fleetable(self):
        # input_scaler is an internal injection from the scaler STEP; a
        # user writing it as an AutoEncoder kwarg must not sneak a
        # different scaling past the declared pipeline
        config = {
            "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            "sklearn.preprocessing.MinMaxScaler",
                            {
                                "gordo_components_tpu.models.AutoEncoder": {
                                    "input_scaler": "standard",
                                }
                            },
                        ]
                    }
                }
            }
        }
        assert extract_fleetable(config) is None

    def test_standard_scaler_with_kwargs_not_fleetable(self):
        # with_mean/with_std overrides deviate from the fleet's z-score fit
        config = {
            "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            {"sklearn.preprocessing.StandardScaler": {"with_mean": False}},
                            "gordo_components_tpu.models.AutoEncoder",
                        ]
                    }
                }
            }
        }
        assert extract_fleetable(config) is None

    def test_bespoke_config_not_fleetable(self):
        bespoke = {
            "gordo_components_tpu.models.LSTMAutoEncoder": {"lookback_window": 8}
        }
        assert extract_fleetable(bespoke) is None

    def test_reference_era_paths_fleetable(self):
        old = {
            "gordo_components.model.anomaly.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            "sklearn.preprocessing.MinMaxScaler",
                            {
                                "gordo_components.model.models.KerasAutoEncoder": {
                                    "kind": "feedforward_hourglass"
                                }
                            },
                        ]
                    }
                }
            }
        }
        assert extract_fleetable(old) == {"kind": "feedforward_hourglass"}

    def test_detector_overrides_not_fleetable(self):
        """Unknown detector kwargs must force the single-build path; the
        honored detector knobs (threshold_quantile/require_thresholds,
        which the fleet now computes identically) stay fleetable."""

        def cfg(**det_kwargs):
            return {
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": FLEETABLE[
                        "gordo_components_tpu.models.DiffBasedAnomalyDetector"
                    ]["base_estimator"],
                    **det_kwargs,
                }
            }

        assert extract_fleetable(cfg(bespoke_detector_knob=1)) is None
        out = extract_fleetable(cfg(threshold_quantile=0.99))
        assert out is not None and out["threshold_quantile"] == 0.99

    def test_scaler_kwargs_not_fleetable(self):
        """A scaler with non-default kwargs (custom feature_range) must not
        take the fleet path, which always fits the default (0, 1) min-max."""
        cfg = {
            "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            {
                                "sklearn.preprocessing.MinMaxScaler": {
                                    "feature_range": [-1, 1]
                                }
                            },
                            "gordo_components_tpu.models.AutoEncoder",
                        ]
                    }
                }
            }
        }
        assert extract_fleetable(cfg) is None

    def test_unsupported_ae_kwargs_not_fleetable(self):
        """AE kwargs the trainer can't honor (DP, bespoke knobs) must
        force the single-build path instead of being silently dropped —
        while honored knobs like validation_split (and, since the fleet
        resolves losses like BaseEstimator, loss/kl_weight) stay
        fleetable."""

        def cfg(ae_kwargs):
            return {
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {"gordo_components_tpu.models.AutoEncoder": ae_kwargs},
                            ]
                        }
                    }
                }
            }

        for bad in ({"bespoke_knob": 1}, {"data_parallel": True}):
            assert extract_fleetable(cfg(bad)) is None
        assert extract_fleetable(cfg({"loss": "mse"})) is not None
        # validation_split is honored by FleetTrainer (val-loss ES parity)
        assert extract_fleetable(cfg({"validation_split": 0.2})) == {
            "validation_split": 0.2
        }

    def test_unscaled_pipeline_not_fleetable(self):
        """A pipeline without a scaler step must not be silently min-max
        scaled by the fleet engine."""
        cfg = {
            "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            {"gordo_components_tpu.models.AutoEncoder": {"epochs": 1}}
                        ]
                    }
                }
            }
        }
        assert extract_fleetable(cfg) is None
        # bare base estimator likewise
        bare = {
            "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "gordo_components_tpu.models.AutoEncoder": {"epochs": 1}
                }
            }
        }
        assert extract_fleetable(bare) is None


class TestBuildFleet:
    def _machines(self, n):
        return [
            Machine(name=f"machine-{i}", dataset=dict(DATASET), model=FLEETABLE)
            for i in range(n)
        ]

    @pytest.fixture(scope="class")
    def built(self, tmp_path_factory):
        """One three-machine gang build shared by the tests that only
        read its results."""
        root = tmp_path_factory.mktemp("fleet-build")
        results = build_fleet(
            self._machines(3),
            str(root / "out"),
            model_register_dir=str(root / "reg"),
        )
        return root, results

    def test_fleet_path_builds_artifacts(self, built):
        tmp_path, results = built
        assert set(results) == {"machine-0", "machine-1", "machine-2"}
        for name, path in results.items():
            model = serializer.load(path)
            md = serializer.load_metadata(path)
            assert md["model"]["fleet_trained"]
            assert md["name"] == name
            # loaded artifact scores anomalies like a single-built one
            import numpy as np

            adf = model.anomaly(np.random.rand(20, 3).astype("float32"))
            assert ("total-anomaly-scaled", "") in adf.columns
            # real tag names (not feature-i) flow through the fleet path
            assert model.tags_ == ["a", "b", "c"]
            # mirrored into output_dir for the serving volume
            assert os.path.exists(tmp_path / "out" / name / "model.pkl")

    def test_manifest_carries_device_and_bucket_provenance(self, built):
        """The manifest says what the trainer resolved and where it ran —
        read from the trained arrays' own devices — so a parent process
        that stays off JAX can report its child's device."""
        import jax

        _, report = built
        manifest = report.manifest()
        dev = jax.devices()[0]
        # the default trainer mesh spans every device of the rig
        assert manifest["device"] == {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        }
        (bucket,) = manifest["buckets"]
        assert bucket["model_type"] == "AutoEncoder"
        assert bucket["n_members"] == 3 and bucket["n_features"] == 3
        assert bucket["layout"] == "legacy"  # dense buckets never time-major
        assert bucket["device"] == manifest["device"]
        assert manifest["gang_width"] == report.gang_width

    def test_cache_hit_on_rerun(self, tmp_path):
        machines = self._machines(2)
        kwargs = dict(
            output_dir=str(tmp_path / "out"),
            model_register_dir=str(tmp_path / "reg"),
        )
        r1 = build_fleet(machines, **kwargs)
        mtimes = {
            n: os.path.getmtime(os.path.join(p, "model.pkl")) for n, p in r1.items()
        }
        r2 = build_fleet(machines, **kwargs)
        assert r1 == r2
        for n, p in r2.items():
            assert os.path.getmtime(os.path.join(p, "model.pkl")) == mtimes[n]

    def test_mixed_fleet_and_bespoke(self, tmp_path):
        machines = self._machines(2)
        machines.append(
            Machine(
                name="bespoke",
                dataset=dict(DATASET),
                model={
                    "gordo_components_tpu.models.AutoEncoder": {
                        "epochs": 1,
                        "batch_size": 64,
                    }
                },
            )
        )
        results = build_fleet(machines, str(tmp_path / "out"))
        assert set(results) == {"machine-0", "machine-1", "bespoke"}


def test_distributed_gang_uses_local_device_mesh(tmp_path, monkeypatch):
    """ADVICE r1 (high): with members partitioned per host, the trainer
    mesh must span only THIS host's devices — a global mesh would place
    host-local data onto non-addressable shardings on a real pod. On a
    single host local == global, so fake a 4-device "host" subset: a
    regression back to the global mesh then fails the assertion."""
    import jax

    import gordo_components_tpu.builder.fleet_build as fb
    from gordo_components_tpu.parallel.fleet import FleetTrainer
    from gordo_components_tpu.workflow.config import Machine

    monkeypatch.setattr(
        "gordo_components_tpu.parallel.distributed.initialize_distributed",
        lambda *a, **k: True,
    )
    host_devices = jax.devices()[:4]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: host_devices)
    captured = {}
    orig_init = FleetTrainer.__init__

    def spy_init(self, *a, **k):
        captured["mesh"] = k.get("mesh")
        return orig_init(self, *a, **k)

    monkeypatch.setattr(FleetTrainer, "__init__", spy_init)

    machines = [
        Machine(
            name="m-0",
            dataset={
                "type": "RandomDataset",
                "train_start_date": "2020-01-01T00:00:00Z",
                "train_end_date": "2020-01-01T06:00:00Z",
                "tag_list": ["a", "b"],
            },
            model={
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {
                                    "gordo_components_tpu.models.AutoEncoder": {
                                        "epochs": 1,
                                        "batch_size": 64,
                                    }
                                },
                            ]
                        }
                    }
                }
            },
        )
    ]
    fb.build_fleet(machines, str(tmp_path / "out"), distributed=True)
    mesh = captured["mesh"]
    assert mesh is not None
    assert list(mesh.devices.flat) == host_devices  # NOT all 8 devices
