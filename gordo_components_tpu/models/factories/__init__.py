"""Architecture factories returning Flax modules.

Reference parity: gordo_components/model/factories/ (unverified; SURVEY.md
§2 "model.factories") — ``feedforward_model`` / ``feedforward_symmetric`` /
``feedforward_hourglass`` and the ``lstm_*`` trio, plus the extended zoo
(Conv1D, variational) named in BASELINE.json config 4.

Importing this package registers every factory.
"""

from gordo_components_tpu.models.factories.feedforward import (
    feedforward_model,
    feedforward_symmetric,
    feedforward_hourglass,
    hourglass_calc_dims,
)
from gordo_components_tpu.models.factories.lstm import (
    lstm_model,
    lstm_symmetric,
    lstm_hourglass,
)
from gordo_components_tpu.models.factories.conv import conv1d_autoencoder
from gordo_components_tpu.models.factories.variational import feedforward_variational
from gordo_components_tpu.models.factories.trunk import sparse_moe_decoder

__all__ = [
    "feedforward_model",
    "feedforward_symmetric",
    "feedforward_hourglass",
    "hourglass_calc_dims",
    "lstm_model",
    "lstm_symmetric",
    "lstm_hourglass",
    "conv1d_autoencoder",
    "feedforward_variational",
    "sparse_moe_decoder",
]
