"""Median of the bank's ``device_wait`` span (host clock): the launch
returned -> ``block_until_ready`` returns, the second part of
``device_execute``: the device program and the fence's wake-up."""

from harness import common


def read(obs):
    return common.median_span_ms(obs, "device_wait")
