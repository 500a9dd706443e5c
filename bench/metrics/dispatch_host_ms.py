"""Serving dispatch: median, over the window's ``serve.dispatch`` spans,
of the span's duration less its ``serve.device_wait`` children, in ms:
the host time of a dispatch (prep, enqueue, resolve and what falls
between them)."""
import numpy as np

from bench import engine_spans


def read(rec):
    if rec.get("kind") != "serve":
        return None
    win = engine_spans.window()
    if win is None:
        return None
    _, dispatches = win
    return 1e3 * float(np.median([
        d.duration - sum(c.duration for c in kids
                         if c.name == "serve.device_wait")
        for d, kids in dispatches]))
