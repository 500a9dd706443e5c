"""ResNet with basic blocks (He et al., arXiv:1512.03385, Table 1; as
torchvision ``resnet18``): a 7x7/2 stem, a 3x3/2 max-pool, stages of two
basic blocks, 1x1/2 projection shortcuts where the shape changes, global
average pooling and a dense head.  BatchNorm is folded into the conv
weights and biases, as at inference.

Convs use XLA's SAME padding (the program's ``ConvSpec`` offers SAME and
VALID): for the stem and the 3x3/2 transitions the window starts one
pixel later than torchvision's symmetric padding; the work is the same.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp


def _algo(cfg: Dict, kernel: int, stride: int) -> str:
    return cfg["algo"]["3x3s1" if (kernel, stride) == (3, 1) else "other"]


def layers(cfg: Dict) -> List[Dict]:
    h = cfg["image_size"]
    w0 = cfg["widths"][0]
    out = [dict(name="stem", kernel=cfg["stem_kernel"], stride=2,
                cin=cfg["in_channels"], cout=w0, h=h, w=h,
                algo=_algo(cfg, cfg["stem_kernel"], 2))]
    h = -(-h // 2)          # stem
    h = -(-h // 2)          # 3x3/2 max-pool
    cin = w0
    for si, (n, width) in enumerate(zip(cfg["stages"], cfg["widths"])):
        for bi in range(n):
            s = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            ho = -(-h // s)
            out.append(dict(name=f"{name}.conv1", kernel=3, stride=s,
                            cin=cin, cout=width, h=h, w=h,
                            algo=_algo(cfg, 3, s)))
            out.append(dict(name=f"{name}.conv2", kernel=3, stride=1,
                            cin=width, cout=width, h=ho, w=ho,
                            algo=_algo(cfg, 3, 1)))
            if s != 1 or cin != width:
                out.append(dict(name=f"{name}.proj", kernel=1, stride=s,
                                cin=cin, cout=width, h=h, w=h,
                                algo=_algo(cfg, 1, s)))
            cin, h = width, ho
    return out


def head(cfg: Dict):
    return {"cin": cfg["widths"][-1], "cout": cfg["n_classes"]}


def network(cfg: Dict, conv: Callable, dense: Callable, x):
    """``conv(name, h)`` runs the named conv (bias included);
    ``dense(h)`` the head.  Returns the logits."""
    h = jax.nn.relu(conv("stem", x))
    # torchvision's 3x3/2 max-pool with padding 1
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    cin = cfg["widths"][0]
    for si, (n, width) in enumerate(zip(cfg["stages"], cfg["widths"])):
        for bi in range(n):
            s = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            y = jax.nn.relu(conv(f"{name}.conv1", h))
            y = conv(f"{name}.conv2", y)
            sc = conv(f"{name}.proj", h) if (s != 1 or cin != width) else h
            h = jax.nn.relu(y + sc)
            cin = width
    return dense(jnp.mean(h, axis=(1, 2)))
