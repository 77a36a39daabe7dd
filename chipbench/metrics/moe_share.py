"""MoE: leaf-operation device time under the ``mlp`` scope (the MoE FFN:
routing, the experts, the combine and its psum) over all leaf-operation
device time of the ``decode`` and ``decode_horizon`` modules in the traced
window, per chip, in percent (``chipbench/decode_time.py``)."""
from chipbench import decode_time


def read(win, cell, peaks):
    secs = decode_time.decode_seconds(win)
    if secs is None:
        return None
    mlp = decode_time.scope_seconds(secs, "mlp")
    return 100.0 * mlp / sum(secs.values()) if mlp > 0 else None
