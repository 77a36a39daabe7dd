"""Device: the share of the traced window in which no operation ran on
the device, in percent."""
from chipbench import trace


def read(win, cell, peaks):
    return trace.idle_share(win)
