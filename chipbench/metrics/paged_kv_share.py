"""Paged KV: leaf-operation device time under the ``paged_kv/*`` scopes
over all leaf-operation device time of the ``decode`` and
``decode_horizon`` modules in the traced window, in percent
(``chipbench/spans.py``)."""
from chipbench import spans


def read(win, cell, peaks):
    got = spans.read_run(win)
    return None if got is None else got["paged_kv_share"]
