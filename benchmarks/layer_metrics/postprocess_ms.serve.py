"""Median of the server's own ``postprocess`` stage span (host clock), over the
request traces the server retained in the window."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "postprocess")
