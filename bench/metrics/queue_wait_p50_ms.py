"""Serving queue: exact median of the served requests'
``Result.queue_wait_ms`` (arrival to dispatch, the engine's stamps)."""
import numpy as np


def read(rec):
    if rec.get("kind") != "serve" or not rec["queue_wait_ms"]:
        return None
    return float(np.median(rec["queue_wait_ms"]))
