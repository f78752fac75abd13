"""Share of the traced window in which no operation ran on the device."""

from harness import common


def read(obs):
    return common.idle_share(obs)
