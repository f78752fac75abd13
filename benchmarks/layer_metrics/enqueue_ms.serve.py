"""Median of the bank's ``enqueue`` span (host clock): the bucket program
called -> the call returns, i.e. argument transfer and launch, the first
part of ``device_execute``."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "enqueue")
