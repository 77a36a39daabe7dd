"""MoE: the MoE layers' least time on one chip over their device time
there (leaf operations under the ``mlp`` scope of the ``decode`` and
``decode_horizon`` modules), over the traced decode dispatches, in percent.
Least time is ``max(flops / peak, bytes / bandwidth)`` of
``moe_decode_work`` in the cell's model file: each emitted token's routed
work divided over the chips, and the chip's held experts and the router
read once per in-graph step."""
from chipbench import decode_time, trace


def read(win, cell, peaks):
    secs = decode_time.decode_seconds(win)
    if secs is None:
        return None
    spent = decode_time.scope_seconds(secs, "mlp")
    names = trace.programs()
    least = 0.0
    for st, _ in trace.traced_steps(win, [names["decode"],
                                          names["decode_horizon"]]):
        if st.decode_contexts:
            f, b = cell.model.moe_decode_work(
                cell.config, len(st.decode_contexts), st.decode_steps)
            least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
    return 100.0 * least / spent if spent > 0 and least > 0 else None
