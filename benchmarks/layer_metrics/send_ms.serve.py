"""Median of the server's own ``send`` span (host clock), over the request
traces the server retained in the window: a tensor answer's segments handed
to the connection, from the first to the return of the last write, after
the root span has closed. Nothing where the program records no ``send``."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "send")
