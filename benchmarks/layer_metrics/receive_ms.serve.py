"""Median of the server's own ``receive`` span (host clock), over the request
traces the server retained in the window: inside ``parse``, the wait for the
rest of a tensor body once the headers are in, and its join. Nothing where
the program records no ``receive``."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "receive")
