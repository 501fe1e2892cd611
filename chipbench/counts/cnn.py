"""Operations and minimal bytes of the CNN configurations, from layer shapes.

Counts follow the model as the configuration file describes it
(``chipbench/configs/<name>.json``): 3x3 SAME convolutions, 2x2 max
pools after the listed convolutions, and for residual models a 3x3
stride-2 projection beside the strided convolution at each change of
width.  A multiply-add counts as two operations.  Bias adds, ReLUs,
pools and the loss are left out: they are a small share and run in
fused elementwise passes, so leaving them out keeps every roofline
share a lower bound.

Minimal bytes are float32 reads of each operand and writes of each
result, once per call, with weights read once per client (every client
holds its own copy).

The family's counts (`cells.family`) take the traffic beside the
configuration; a CNN's counts do not depend on it.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

F32 = 4
BITS = 32


def layers(cfg: Dict) -> List[Dict]:
    """One record per matmul-like op of a forward pass over one sample.

    Keys: ``kind`` (``conv``/``dense``), ``unit`` (the cuttable layer it
    belongs to), ``cin``, ``cout``, ``hw_in``, ``hw_out``, ``k`` (kernel
    side), ``first`` (its input is the image, so no input gradient).
    """
    out = []
    hw = cfg["image_size"]
    cin = cfg["in_channels"]
    pools = set(cfg.get("pool_after", []))
    residual = cfg.get("residual", False)
    for i, c in enumerate(cfg["conv_channels"]):
        stride = 2 if residual and i > 0 and c != cin else 1
        hw_out = -(-hw // stride)
        out.append(dict(kind="conv", unit=i, cin=cin, cout=c, hw_in=hw,
                        hw_out=hw_out, k=3, first=i == 0))
        if stride == 2:
            out.append(dict(kind="conv", unit=i, cin=cin, cout=c, hw_in=hw,
                            hw_out=hw_out, k=3, first=False))
        hw = hw_out
        cin = c
        if i + 1 in pools:
            hw = max(1, hw // 2)
    prev = cin if residual else cin * hw * hw
    n_conv = len(cfg["conv_channels"])
    for j, f in enumerate(list(cfg["fc_dims"]) + [cfg["n_classes"]]):
        out.append(dict(kind="dense", unit=n_conv + j, cin=prev, cout=f,
                        hw_in=1, hw_out=1, k=1, first=False))
        prev = f
    return out


def _valid_taps(hw_in: int, hw_out: int, k: int) -> int:
    """Kernel taps that land inside the image along one axis, summed over
    output positions, for a SAME convolution (TensorFlow's padding: the
    odd pixel of padding goes after)."""
    stride = -(-hw_in // hw_out)
    pad = max((hw_out - 1) * stride + k - hw_in, 0)
    lo = pad // 2
    return sum(1 for o in range(hw_out) for t in range(k)
               if 0 <= o * stride + t - lo < hw_in)


def fwd_flops(layer: Dict) -> int:
    """Forward operations of one layer for one sample.  Taps that fall on
    the zero padding are not counted: no algorithm needs them, and
    XLA's cost analysis leaves them out too."""
    taps = _valid_taps(layer["hw_in"], layer["hw_out"], layer["k"]) ** 2
    return 2 * taps * layer["cin"] * layer["cout"]


def param_count(cfg: Dict) -> int:
    """Weights and biases of the whole model."""
    return sum(l["k"] ** 2 * l["cin"] * l["cout"] + l["cout"]
               for l in layers(cfg))


def forward_flops(cfg: Dict, traffic: Optional[Dict] = None,
                  kinds=("conv", "dense")) -> int:
    """Forward operations for one sample."""
    return sum(fwd_flops(l) for l in layers(cfg) if l["kind"] in kinds)


def train_flops(cfg: Dict, traffic: Optional[Dict] = None,
                kinds=("conv", "dense")) -> int:
    """Forward plus backward operations for one sample.

    The backward pass makes a weight gradient (as many operations as the
    forward) and an input gradient (the same again) for every layer but
    the first, whose input is the image.
    """
    total = 0
    for l in layers(cfg):
        if l["kind"] in kinds:
            f = fwd_flops(l)
            total += f * (2 if l["first"] else 3)
    return total


def profile(cfg: Dict, traffic: Optional[Dict] = None) -> Dict[str, np.ndarray]:
    """Per-unit costs of the configuration, cumulative where noted, as
    `reference.control` walks them.  For a cut after unit j (1-based, as
    the decisions count): ``rho`` the cumulative forward operations per
    sample (every tap of every 3x3 kernel at every output position, the
    usual count; a residual unit's 3x3 stride-2 projection counted as
    built), ``bwd`` twice that, ``psi``/``chi`` the activation (and its
    gradient) leaving unit j, in bits, ``delta`` the bits of units
    1..j's parameters, and ``params`` each unit's parameter count.
    Sizes are float32."""
    flops, params, act = [], [], []
    hw, cin = cfg["image_size"], cfg["in_channels"]
    pools = set(cfg.get("pool_after", []))
    residual = cfg.get("residual", False)
    for i, c in enumerate(cfg["conv_channels"]):
        strided = residual and i > 0 and c != cin
        if strided:
            hw = -(-hw // 2)
        convs = 2 if strided else 1       # the projection is a 3x3 conv too
        flops.append(convs * 2 * 9 * cin * c * hw * hw)
        params.append(convs * (9 * cin * c + c))
        cin = c
        if i + 1 in pools:
            hw = max(1, hw // 2)
        act.append(c * hw * hw)
    prev = cin if residual else cin * hw * hw
    for f in list(cfg["fc_dims"]) + [cfg["n_classes"]]:
        flops.append(2 * prev * f)
        params.append(prev * f + f)
        act.append(f)
        prev = f
    flops = np.asarray(flops, np.float64)
    psi = np.asarray(act, np.float64) * BITS
    return {"rho": np.cumsum(flops), "bwd": np.cumsum(2.0 * flops),
            "psi": psi, "chi": psi.copy(),
            "delta": np.cumsum(np.asarray(params, np.float64)) * BITS,
            "params": np.asarray(params, np.float64)}


def _io_elems(layer: Dict, batch: int):
    x = batch * layer["hw_in"] ** 2 * layer["cin"]
    y = batch * layer["hw_out"] ** 2 * layer["cout"]
    w = layer["k"] ** 2 * layer["cin"] * layer["cout"]
    return x, y, w


def conv_passes(cfg: Dict, batches, *, train: bool) -> List[tuple]:
    """``(flops, bytes)`` of every convolution pass over a set of batches.

    ``batches`` lists the real rows of each call that has its own
    weights: one entry per client for training, one entry for an eval
    of the aggregated model.  A training call makes three passes per
    convolution (forward, input gradient, weight gradient; no input
    gradient for the first), an eval one.
    """
    out = []
    for l in layers(cfg):
        if l["kind"] != "conv":
            continue
        f1 = fwd_flops(l)
        for b in batches:
            if b <= 0:
                continue
            x, y, w = _io_elems(l, b)
            out.append((f1 * b, F32 * (x + w + y)))
            if train:
                if not l["first"]:
                    out.append((f1 * b, F32 * (y + w + x)))
                out.append((f1 * b, F32 * (x + y + w)))
    return out


def roofline_seconds(passes, peak_flops: float, peak_bw: float) -> float:
    """Least time the passes can take: each pass bound by the larger of
    its operations over peak and its bytes over bandwidth."""
    return sum(max(f / peak_flops, b / peak_bw) for f, b in passes)
