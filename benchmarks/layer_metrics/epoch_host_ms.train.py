"""Host time of a fit between its epoch dispatches: the sum of the
trainer's ``epoch_host`` spans (early stopping, histories, checkpoint
hand-off), per fit; median over the window's fits."""

from harness import fit_spans


def read(obs):
    return fit_spans.median_ms(obs, ("epoch_host",))
