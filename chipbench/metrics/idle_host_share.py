"""Scheduler: the share of the traced window in which no operation ran on
the device while the host did other engine work (the innermost host span
any other ``engine.*`` span), in percent (``chipbench/spans.py``)."""
from chipbench import spans


def read(win, cell, peaks):
    return spans.idle_share_of(win, "engine")
