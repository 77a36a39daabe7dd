"""Programs: the least time for each admitted prompt's real (unpadded)
work over the device time of ``prefill_slot``, in percent
(``chipbench/trace.py``)."""
from chipbench import trace


def read(win, cell, peaks):
    return trace.prefill_share(win, cell, peaks)
