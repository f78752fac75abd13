"""Median of the server's root span of a scoring request (``anomaly``: the
middleware opens it before the handler and closes it when the handler
returns), over the request traces the server retained in the window. What
``score_p50_ms`` holds beyond it is the client's, the socket's and the
generator's."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "anomaly")
