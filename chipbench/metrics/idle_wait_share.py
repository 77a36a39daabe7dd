"""Executor: the share of the traced window in which no operation ran on
the device while the host was blocked on a program's result (the innermost
host span an ``engine.wait.*``), in percent (``chipbench/spans.py``)."""
from chipbench import spans


def read(win, cell, peaks):
    return spans.idle_share_of(win, "wait")
