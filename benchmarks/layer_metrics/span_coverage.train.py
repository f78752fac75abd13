"""Share (%) of the window's ``fit:<bucket>`` spans that the trainer's
stage spans account for. The stages of a bucket follow one another, so what
is missing from 100 is time no span names."""

from harness import fit_spans


def read(obs):
    fits = fit_spans.window_fits(obs)
    whole = sum(fit.get(fit_spans.BUCKET, 0.0) for fit in fits)
    staged = sum(fit[name] for fit in fits for name in fit_spans.STAGES if name in fit)
    if not whole or not staged:
        return None
    return 100.0 * staged / whole
