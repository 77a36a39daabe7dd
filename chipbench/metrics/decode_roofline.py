"""Programs: the least time for the weights and the live keys and values
that each traced decode dispatch needed, over the device time of
``decode`` and ``decode_horizon``, in percent (``chipbench/trace.py``)."""
from chipbench import trace


def read(win, cell, peaks):
    return trace.decode_share(win, cell, peaks)
