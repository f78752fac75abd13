"""Median of the ``resolve`` span (host clock): the bank's ``postprocess``
done on the executor thread -> the view coroutine resumes, i.e. the
hand-off back to the event loop and the future's wake-up. A stall that
holds the event loop lands here."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "resolve")
