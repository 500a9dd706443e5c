"""A tiny separable network for the harness's tests, entered into
``sys.modules`` as ``bench.graphs.tiny_separable`` by the test that runs
it: a depthwise 3x3/1, a 1x1, a depthwise 3x3/2 and a ``groups`` 3x3/1
conv with a residual add around it, each followed by ReLU.  The output is
the last feature map, so the check compares every pixel of it."""
from __future__ import annotations

from typing import Callable, Dict, List

import jax


def layers(cfg: Dict) -> List[Dict]:
    h, c = cfg["image_size"], cfg["in_channels"]
    w = cfg["width"]
    ho = -(-h // 2)
    return [
        dict(name="dw1", kernel=3, stride=1, cin=c, cout=c, groups=c,
             h=h, w=h, algo=cfg["algo"]["depthwise"]),
        dict(name="pw1", kernel=1, stride=1, cin=c, cout=w, h=h, w=h,
             algo=cfg["algo"]["other"]),
        dict(name="dw2", kernel=3, stride=2, cin=w, cout=w, groups=w,
             h=h, w=h, algo=cfg["algo"]["depthwise"]),
        dict(name="g", kernel=3, stride=1, cin=w, cout=w,
             groups=cfg["groups"], h=ho, w=ho, algo=cfg["algo"]["other"]),
    ]


def head(cfg: Dict):
    del cfg
    return None


def network(cfg: Dict, conv: Callable, dense: Callable, x):
    """``conv(name, h)`` runs the named conv (bias included)."""
    del cfg, dense
    h = jax.nn.relu(conv("dw1", x))
    h = jax.nn.relu(conv("pw1", h))
    h = jax.nn.relu(conv("dw2", h))
    return jax.nn.relu(h + conv("g", h))
