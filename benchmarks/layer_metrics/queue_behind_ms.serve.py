"""Median of the engine's ``queue_behind`` span (host clock): a request
enqueued -> the engine loop back at the queue, i.e. the wait behind the one
batch in flight. The rest of ``queue_wait`` is the deliberate flush window
(``queue_flush``)."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "queue_behind")
