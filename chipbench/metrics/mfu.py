"""Model step: model FLOPs of all prefill and decode work in the traced
steps over the serving programs' device time at the chip's peak, in
percent (``chipbench/trace.py``)."""
from chipbench import trace


def read(win, cell, peaks):
    return trace.model_flops_share(win, cell, peaks)
