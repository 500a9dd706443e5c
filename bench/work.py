"""The benchmark's count of the work in a conv layer, from its shapes alone.

A layer with ``groups`` g (default 1) pairs each output channel with
``C_in / g`` input channels, so every count below uses ``C_in / g`` where
a dense layer has ``C_in``.  A depthwise layer (g = C_in = C_out) pairs
each output channel with one input channel.

Counted operations (``counted_ops``)
    A 3x3 stride-1 conv counts ``2 * (t^2 / M^2) * H' * W' * (C_in / g) *
    C_out``, with ``t`` and ``M`` taken from the configuration's
    ``counted_algo`` (``sfc6_6``: t = 10, M = 6): the ideal SFC
    multiplication count, with no tile ceiling and no channel padding.
    Every other conv (the 7x7/2 stem, the 3x3/2 transitions, the 1x1
    projections) counts ``2 * H' * W' * R^2 * (C_in / g) * C_out``, twice
    its direct MACs.

Least HBM bytes (``least_bytes``)
    The f32 input read once, the ``R^2 * (C_in / g) * C_out`` int8 weights
    read once per call, and the f32 output written once.

Why the count is fixed by the configuration and not by the plan that runs:
a PR that changes the algorithm, the datapath or the lowering is then
measured against the same work.  Counting direct-conv operations for the
3x3 layers instead would let a sound SFC kernel read over 105% of the int8
peak once the MXU passes about 31% busy, since SFC does 3.24x fewer
multiplications (9 / (100/36)).  A roofline share is the least time, the
larger of ``counted_ops / int8 peak`` and ``least_bytes / HBM bandwidth``,
over the measured device time.

Depthwise products run on the vector unit, not the MXU, yet their
operations are divided by the int8 MXU peak like every other layer's.  For
a depthwise layer the compute term is then a lower bound on its time, so
it can only lower the layer's share, never carry it past 100%.
"""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def out_extent(size: int, stride: int) -> int:
    """Output extent of a SAME-padded conv."""
    return -(-size // stride)


def groups(layer: Dict) -> int:
    """A layer's ``groups``: 1 where the layer record has none."""
    return layer.get("groups", 1)


def _cin_per_group(layer: Dict) -> int:
    return layer["cin"] // groups(layer)


def direct_macs(layer: Dict) -> int:
    """Direct-convolution multiply-accumulates for one image."""
    ho = out_extent(layer["h"], layer["stride"])
    wo = out_extent(layer["w"], layer["stride"])
    r = layer["kernel"]
    return ho * wo * r * r * _cin_per_group(layer) * layer["cout"]


def counted_ops(layer: Dict, counted_algo: Dict) -> float:
    """Counted operations of one image through ``layer`` (see module doc)."""
    if layer["kernel"] == counted_algo["R"] and layer["stride"] == 1:
        t, m = counted_algo["t"], counted_algo["M"]
        return 2.0 * (t * t) / (m * m) * layer["h"] * layer["w"] \
            * _cin_per_group(layer) * layer["cout"]
    return 2.0 * direct_macs(layer)


def least_bytes(layer: Dict, batch: int) -> float:
    """Least HBM traffic of one call at ``batch`` images (see module doc)."""
    ho = out_extent(layer["h"], layer["stride"])
    wo = out_extent(layer["w"], layer["stride"])
    r = layer["kernel"]
    x = layer["h"] * layer["w"] * layer["cin"] * 4
    y = ho * wo * layer["cout"] * 4
    return float(batch * (x + y)
                 + r * r * _cin_per_group(layer) * layer["cout"])


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; a missing device raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]


def least_time_s(layer: Dict, counted_algo: Dict, batch: int,
                 peaks: Dict[str, float]):
    """(seconds, bound) of one call: the larger of compute and bytes time,
    and which of the two it is ('compute' or 'bytes')."""
    compute = batch * counted_ops(layer, counted_algo) \
        / peaks["int8_ops_per_s"]
    memory = least_bytes(layer, batch) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "bytes")
