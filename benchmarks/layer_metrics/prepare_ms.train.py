"""Host time of a fit before its first epoch: the trainer's ``stack_pad`` +
``to_device`` + ``scaler_fit`` + ``init_state`` spans, per fit; median over
the window's fits."""

from harness import fit_spans


def read(obs):
    return fit_spans.median_ms(obs, fit_spans.PREPARE)
