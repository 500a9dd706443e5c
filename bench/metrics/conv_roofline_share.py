"""Kernels: the least time of every conv call in the traced window (per
call the larger of counted ops over the int8 peak and least bytes over
HBM bandwidth, ``bench/work.py``) over the device time under the conv
layers' named scopes, in %."""
from bench import work


def read(rec):
    if rec.get("kind") != "offline":
        return None
    scope_s = rec["trace"]["scope_s"]
    least = measured = 0.0
    for name, layer in rec["layers"].items():
        if name not in scope_s:
            continue
        t, _ = work.least_time_s(layer, rec["counted_algo"], rec["batch"],
                                 rec["peaks"])
        least += t * rec["forwards"]
        measured += scope_s[name]
    return 100.0 * least / measured if measured > 0 else None
