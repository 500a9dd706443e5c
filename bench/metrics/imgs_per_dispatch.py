"""Serving batch forming: mean requests per engine dispatch over the
window's dispatches, from ``Engine.snapshot()``'s occupancy record less
the set-up's warm-up dispatches."""


def read(rec):
    if rec.get("kind") != "serve":
        return None
    return rec["imgs_per_dispatch"]
