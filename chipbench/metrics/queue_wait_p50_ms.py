"""Scheduler: median wait from a request's due time to the start of its
``prefill_slot`` dispatch, in ms, over the requests admitted inside the
window.  Read from the engine's ``trace=`` hook (each dispatch's host
stamp less its wall time)."""
import numpy as np


def read(win, cell, peaks):
    waits = [r.t_prefill_start - r.due for r in win.requests.values()
             if r.t_prefill_start is not None
             and r.t_prefill_start <= win.t_end]
    return 1e3 * float(np.median(waits)) if waits else None
