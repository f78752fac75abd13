"""Per-member hyperparameter vectors in the fleet engine (VERDICT r3 next
#7; SURVEY.md §7 hard part 4 "per-model LR").

Learning rate rides the injected opt state as a stacked (M,) leaf and ES
patience rides the (M,) carry, so members differing only in those knobs
train in ONE vmap program — with EXACT parity against a scalar-knob gang
of the same width (same member index -> same init rng -> bitwise-equal
training)."""

import numpy as np
import pytest

import jax

from gordo_components_tpu import serializer
from gordo_components_tpu.builder.fleet_build import _group_key, build_fleet
from gordo_components_tpu.parallel.fleet import FleetTrainer
from gordo_components_tpu.workflow.config import Machine


def _data(n=2, rows=100, f=4):
    rng = np.random.RandomState(0)
    return {
        name: rng.rand(rows, f).astype("float32")
        for name in [chr(ord("a") + i) for i in range(n)]
    }


def _leaves_equal(a, b):
    return all(
        np.array_equal(x, y)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


class TestPerMemberLR:
    def test_exact_parity_vs_scalar_gangs(self):
        """Member i of a mixed-LR gang must train bitwise-identically to
        member i of a same-width gang with that LR as the scalar."""
        data = _data()
        kw = dict(kind="feedforward_symmetric", dims=[4], epochs=4, batch_size=32)
        mixed = FleetTrainer(**kw).fit(
            dict(data),
            member_hparams={
                "a": {"learning_rate": 1e-3},
                "b": {"learning_rate": 5e-3},
            },
        )
        lo = FleetTrainer(**kw, learning_rate=1e-3).fit(dict(data))
        hi = FleetTrainer(**kw, learning_rate=5e-3).fit(dict(data))
        assert _leaves_equal(mixed["a"].params, lo["a"].params)
        assert _leaves_equal(mixed["b"].params, hi["b"].params)
        assert mixed["a"].history["loss"] == lo["a"].history["loss"]
        assert mixed["b"].history["loss"] == hi["b"].history["loss"]
        # and the two LRs genuinely trained differently
        assert mixed["a"].history["loss"] != mixed["b"].history["loss"]

    def test_validation(self):
        data = _data(1)
        t = FleetTrainer(kind="feedforward_symmetric", dims=[4], epochs=1)
        with pytest.raises(ValueError, match="unknown member"):
            t.fit(dict(data), member_hparams={"ghost": {"learning_rate": 1.0}})
        with pytest.raises(ValueError, match="unsupported keys"):
            t.fit(dict(data), member_hparams={"a": {"epochs": 3}})
        with pytest.raises(ValueError, match="ES disabled"):
            t.fit(
                dict(data),
                member_hparams={"a": {"early_stopping_patience": 2}},
            )


class TestPerMemberPatience:
    def _fit(self):
        rng = np.random.RandomState(1)
        data = {
            "impatient": rng.rand(120, 3).astype("float32"),
            "patient": rng.rand(120, 3).astype("float32"),
        }
        # min_delta larger than any real per-epoch improvement: after the
        # first epoch nothing counts as improved, so the stop epoch is
        # EXACTLY patience + 1 — the knob under test
        return FleetTrainer(
            kind="feedforward_symmetric",
            dims=[2],
            epochs=40,
            batch_size=64,
            early_stopping_patience=1,
            early_stopping_min_delta=10.0,
        ).fit(
            data,
            member_hparams={
                "impatient": {"early_stopping_patience": 1},
                "patient": {"early_stopping_patience": 8},
            },
        )

    def test_patience_vector_host_path(self):
        out = self._fit()
        assert len(out["impatient"].history["loss"]) == 2
        assert len(out["patient"].history["loss"]) == 9


class TestGangGrouping:
    def test_group_key_merges_lr_and_patience_values(self):
        base = {"kind": "feedforward_hourglass", "epochs": 3}
        assert _group_key(dict(base, learning_rate=1e-3)) == _group_key(
            dict(base, learning_rate=9e-3)
        )
        assert _group_key(
            dict(base, early_stopping_patience=2)
        ) == _group_key(dict(base, early_stopping_patience=7))
        # ES presence still splits (different programs)
        assert _group_key(dict(base, early_stopping_patience=2)) != _group_key(
            base
        )
        # explicit None == omitted == ES off: same gang
        assert _group_key(
            dict(base, early_stopping_patience=None)
        ) == _group_key(base)
        # anything else still splits
        assert _group_key(dict(base, epochs=4)) != _group_key(base)

    def test_build_fleet_one_gang_two_lrs(self, tmp_path):
        dataset = {
            "type": "RandomDataset",
            "train_start_date": "2020-01-01T00:00:00Z",
            "train_end_date": "2020-01-01T12:00:00Z",
            "tag_list": ["a", "b", "c"],
        }

        def model(lr):
            return {
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {
                                    "gordo_components_tpu.models.AutoEncoder": {
                                        "kind": "feedforward_symmetric",
                                        "dims": [4],
                                        "epochs": 2,
                                        "batch_size": 64,
                                        "learning_rate": lr,
                                    }
                                },
                            ]
                        }
                    }
                }
            }

        machines = [
            Machine(name="m-lo", dataset=dict(dataset), model=model(1e-3)),
            Machine(name="m-hi", dataset=dict(dataset), model=model(8e-3)),
        ]
        results = build_fleet(machines, str(tmp_path / "out"))
        stats = [
            serializer.load_metadata(p)["model"]["fleet_stats"]
            for p in results.values()
        ]
        # ONE gang of two members — not two single-member gangs
        assert all(s["n_members"] == 2 for s in stats)
        # both artifacts load and score
        for p in results.values():
            model_obj = serializer.load(p)
            model_obj.anomaly(np.random.rand(10, 3).astype("float32"))

        # partial cache hit: build m-lo alone into a registry, then rerun
        # the pair — the cached member must not leak hparams for a member
        # the trainer isn't given (regression: ValueError 'unknown member')
        reg = str(tmp_path / "reg")
        build_fleet([machines[0]], str(tmp_path / "out2"), model_register_dir=reg)
        results2 = build_fleet(
            machines, str(tmp_path / "out3"), model_register_dir=reg
        )
        assert set(results2) == {"m-lo", "m-hi"}
        # the uncached member trained in a 1-member gang this time
        md_hi = serializer.load_metadata(results2["m-hi"])["model"]
        assert md_hi["fleet_stats"]["n_members"] == 1
