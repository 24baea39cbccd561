"""Plain reference of an integer CNN (the PULP-NN layer set): NumPy on
the host, nothing of the program.

Every boundary is an unsigned ``a_bits`` integer image. A conv sums
integer products exactly (float32 matrix products of small integers:
every partial sum stays below 2^24, since |x| <= 127, |w| <= 127 and a
conv sums at most 3 * 3 * 64 products, 9.3M),
then applies the integer batch-norm and requantization of the paper's
eqs. 3 and 4: ``y = clip(((acc * kappa + lam) * m) >> d, 0, 2^a - 1)``
with ``>>`` the floor shift (int64, so the 47-bit product is exact). A
residual add is ``clip((m1 * a + m2 * b) >> d)``, global average pooling
``clip((sum * m) >> d)``, and the head's raw integer logits are returned.

Activations on an 8-bit grid top out at 127 (int8 containers).
"""
from __future__ import annotations

import numpy as np

_A_MAX = {8: 127, 4: 15, 2: 3}


def quantize_input(images: np.ndarray, eps: float, a_bits: int) -> np.ndarray:
    """Real images in [0, 1) -> integer images, round half to even."""
    return np.clip(np.round(images.astype(np.float64) / eps), 0,
                   _A_MAX[a_bits]).astype(np.int64)


def conv(x: np.ndarray, w: np.ndarray, stride: int, pad: int) -> np.ndarray:
    """Integer conv: x (N, H, W, C), w (k, k, C, O) -> int64 (N, Ho, Wo, O)."""
    n, h, wd, c = x.shape
    k = w.shape[0]
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    ho = (h + 2 * pad - k) // stride + 1
    wo = (wd + 2 * pad - k) // stride + 1
    xp = xp.astype(np.float32)
    acc = np.zeros((n * ho * wo, w.shape[-1]), np.float32)
    for dy in range(k):
        for dx in range(k):
            patch = xp[:, dy:dy + stride * (ho - 1) + 1:stride,
                       dx:dx + stride * (wo - 1) + 1:stride, :]
            acc += patch.reshape(-1, c) @ w[dy, dx].astype(np.float32)
    return np.rint(acc).astype(np.int64).reshape(n, ho, wo, -1)


def requant(phi: np.ndarray, m, d: int, a_bits: int) -> np.ndarray:
    return np.clip((phi * np.asarray(m, np.int64)) >> d, 0, _A_MAX[a_bits])


def forward(net: dict, cfg: dict, x: np.ndarray, a_bits: int) -> np.ndarray:
    """Raw int64 logits (N, classes) of integer images ``x``.

    ``net[path]`` holds the layer's integers: conv {"w" (k, k, C, O),
    "kappa", "lam", "m", "d"}, add {"m1", "m2", "d"}, pool {"m", "d"},
    linear {"w" (C, O)}."""
    stream = x
    edges = {}
    for L in cfg["layers"]:
        p = net.get(L["path"], {})
        xin = edges[L["input_from"]] if L.get("input_from") else stream
        kind = L["kind"]
        if kind == "conv":
            acc = conv(xin, p["w"], L["stride"], L["pad"])
            phi = acc * p["kappa"].astype(np.int64) + p["lam"].astype(
                np.int64)
            y = requant(phi, p["m"], p["d"], a_bits)
        elif kind == "add":
            s = (xin * p["m1"] + edges[L["skip_from"]] * p["m2"]) >> p["d"]
            y = np.clip(s, 0, _A_MAX[a_bits])
        elif kind == "avgpool_global":
            y = requant(xin.sum(axis=(1, 2)), p["m"], p["d"], a_bits)
        elif kind == "linear":
            y = np.rint(xin.astype(np.float32) @ p["w"].astype(np.float32)
                        ).astype(np.int64)
        else:
            raise ValueError(f"{L['path']}: unknown kind {kind!r}")
        if L.get("save_as"):
            edges[L["save_as"]] = y
        if not L.get("branch"):
            stream = y
    return stream
