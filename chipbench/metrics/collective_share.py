"""Collectives: leaf-operation device time of collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
found by instruction name) over all leaf-operation device time of the
``decode`` and ``decode_horizon`` modules in the traced window, per chip,
in percent (``chipbench/decode_time.py``)."""
from chipbench import decode_time


def read(win, cell, peaks):
    secs = decode_time.decode_seconds(win)
    if secs is None:
        return None
    coll = sum(v for (_, op), v in secs.items()
               if decode_time.is_collective(op))
    return 100.0 * coll / sum(secs.values())
