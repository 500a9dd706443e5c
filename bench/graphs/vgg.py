"""VGG (Simonyan & Zisserman, arXiv:1409.1556): 3x3 stride-1 convs with
ReLU, a 2x2/2 max-pool after each stage.  The configuration's
``fc_layers`` is 0, so the network's output is the last pool's feature
map (B, 7, 7, 512) at 224x224."""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp


def layers(cfg: Dict) -> List[Dict]:
    out, h, cin = [], cfg["image_size"], cfg["in_channels"]
    for si, (n, width) in enumerate(zip(cfg["stages"], cfg["widths"])):
        for ci in range(n):
            out.append(dict(name=f"s{si}c{ci}", kernel=3, stride=1, cin=cin,
                            cout=width, h=h, w=h, algo=cfg["algo"]["3x3s1"]))
            cin = width
        h = -(-h // 2)
    return out


def head(cfg: Dict):
    """The dense head made with the weights (None: ``fc_layers`` is 0)."""
    if cfg["fc_layers"]:
        raise ValueError("this VGG assembly runs no fully connected layer")
    return None


def _pool(h):
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def network(cfg: Dict, conv: Callable, dense: Callable, x):
    """``conv(name, h)`` runs the named conv (bias included)."""
    del dense
    h = x
    for si, n in enumerate(cfg["stages"]):
        for ci in range(n):
            h = jax.nn.relu(conv(f"s{si}c{ci}", h))
        h = _pool(h)
    return h
