"""Whole forward: counted operations per image (``bench/work.py``) times
the traced window's images/s, over the device's int8 peak, in %."""


def read(rec):
    if rec.get("kind") != "offline":
        return None
    return 100.0 * rec["ops_per_image"] * rec["images_per_s"] \
        / rec["peaks"]["int8_ops_per_s"]
