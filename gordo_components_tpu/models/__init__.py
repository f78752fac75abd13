"""Model layer: Flax factories + sklearn-compatible estimators + anomaly
wrappers (reference parity: gordo_components/model/, unverified — SURVEY.md
§2)."""

from gordo_components_tpu.models.base import GordoBase
from gordo_components_tpu.models.register import register_model_builder, lookup_factory
from gordo_components_tpu.models.models import (
    AutoEncoder,
    BaseEstimator,
    ConvAutoEncoder,
    LSTMAutoEncoder,
    LSTMForecast,
    TrunkForecast,
)
from gordo_components_tpu.models.anomaly import DiffBasedAnomalyDetector

# Reference-era names accepted as aliases so old configs keep working.
KerasAutoEncoder = AutoEncoder
KerasLSTMAutoEncoder = LSTMAutoEncoder
KerasLSTMForecast = LSTMForecast

__all__ = [
    "GordoBase",
    "register_model_builder",
    "lookup_factory",
    "BaseEstimator",
    "AutoEncoder",
    "LSTMAutoEncoder",
    "LSTMForecast",
    "ConvAutoEncoder",
    "TrunkForecast",
    "DiffBasedAnomalyDetector",
    "KerasAutoEncoder",
    "KerasLSTMAutoEncoder",
    "KerasLSTMForecast",
]
