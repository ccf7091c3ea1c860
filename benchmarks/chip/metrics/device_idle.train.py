"""device_idle.train: share of the traced window in which no operation
ran on the chip (1 - union of device-op intervals / window)."""
from harness.stats import idle_share


def read(obs):
    return idle_share(obs)
