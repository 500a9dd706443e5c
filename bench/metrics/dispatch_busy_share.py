"""Serving dispatch: the window's ``serve.dispatch`` durations summed
over the ``serve.loop`` duration, in %: the share of the dispatch
thread's time spent serving batches rather than waiting for them."""
from bench import engine_spans


def read(rec):
    if rec.get("kind") != "serve":
        return None
    win = engine_spans.window()
    if win is None:
        return None
    loop, dispatches = win
    return 100.0 * sum(d.duration for d, _ in dispatches) / loop.duration
