"""Host time of a fit after its last epoch: the trainer's ``error_scalers``
+ ``unstack`` + ``members`` spans, per fit; median over the window's fits."""

from harness import fit_spans


def read(obs):
    return fit_spans.median_ms(obs, fit_spans.FINISH)
