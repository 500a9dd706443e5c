"""Device, offline cells: 1 - (union of device op intervals / traced
window), in %."""


def read(rec):
    if rec.get("kind") != "offline":
        return None
    return 100.0 * rec["trace"]["idle_share"]
