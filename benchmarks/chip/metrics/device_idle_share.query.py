"""device_idle_share.query: percent of the traced window in which no
operation ran on the chip (device trace)."""
from peakshare import idle_share


def read(run):
    return idle_share(run)
