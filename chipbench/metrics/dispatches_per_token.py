"""Scheduler: decode-path dispatches per decoded token over the window,
from the engine's counters ``decode_steps`` and ``decode_tokens``."""


def read(win, cell, peaks):
    if not win.decode_tokens:
        return None
    return win.decode_steps / win.decode_tokens
