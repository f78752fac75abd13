"""Median of the view's ``encode`` span (host clock): the scored arrays
framed into the response body."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "encode")
