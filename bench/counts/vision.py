"""Counts for the paper's residual networks (NHWC, 3x3 convolutions,
ghost batch norm after every convolution but the projections)."""
from __future__ import annotations

from typing import Dict, NamedTuple

F32 = 4


class Conv(NamedTuple):
    h: int          # output height
    w: int          # output width
    cin: int
    cout: int
    k: int          # kernel size


class Norm(NamedTuple):
    h: int
    w: int
    c: int


def layers(cfg: Dict) -> tuple:
    """(convolutions, batch norms, dense (in, out)) of a ``kind: resnet``
    configuration, in the order they run."""
    if cfg["kind"] != "resnet":
        raise ValueError(f"no counts for kind {cfg['kind']!r}")
    h, w, cin = cfg["input_shape"]
    c0 = cfg["channels"][0]
    convs = [Conv(h, w, cin, c0, 3)]
    norms = [Norm(h, w, c0)]
    cin = c0
    for si, cout in enumerate(cfg["channels"]):
        for bi in range(cfg["blocks_per_stage"]):
            if si > 0 and bi == 0:
                h, w = h // 2, w // 2
            convs.append(Conv(h, w, cin, cout, 3))
            norms.append(Norm(h, w, cout))
            convs.append(Conv(h, w, cout, cout, 3))
            norms.append(Norm(h, w, cout))
            if cin != cout:
                convs.append(Conv(h, w, cin, cout, 1))
            cin = cout
    return convs, norms, (cin, cfg["n_classes"])


def conv_flops(c: Conv) -> float:
    return 2.0 * c.h * c.w * c.cin * c.cout * c.k * c.k


def forward_flops_per_image(cfg: Dict) -> float:
    convs, _, (fin, fout) = layers(cfg)
    return sum(conv_flops(c) for c in convs) + 2.0 * fin * fout


def train_flops_per_image(cfg: Dict) -> float:
    """Forward, weight gradient and input gradient of every convolution and
    of the classifier, the stem's input gradient excepted (the images take
    no gradient). Normalisation and elementwise work is not counted."""
    convs, _, _ = layers(cfg)
    return 3.0 * forward_flops_per_image(cfg) - conv_flops(convs[0])


# The four Pallas passes of one ghost batch norm layer, per element of its
# (ghosts, rows, channels) input, f32: (elements read + written, flops).
GBN_PASSES = {
    "fwd_stats": (1, 3),       # read x; sum and sum of squares
    "fwd_normalize": (2, 4),   # read x, write y; (x - mu) * rstd * g + b
    "bwd_stats": (2, 5),       # read x, dy; sums of dy and dy * xhat
    "bwd_dx": (3, 8),          # read x, dy, write dx
}


def gbn_step(cfg: Dict, batch: int) -> Dict[str, float]:
    """Bytes and flops of every GBN kernel call of one training step at
    ``batch`` images, and the number of calls."""
    _, norms, _ = layers(cfg)
    elems = sum(batch * n.h * n.w * n.c for n in norms)
    return {"calls": len(norms) * len(GBN_PASSES),
            "bytes": elems * F32 * sum(p[0] for p in GBN_PASSES.values()),
            "flops": elems * float(sum(p[1] for p in GBN_PASSES.values()))}
