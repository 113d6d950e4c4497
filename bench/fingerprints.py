"""Client fingerprints: the six synthetic datasets of the paper's setting.

A copy of the program's generators (``repro.data.synthetic`` and
``repro.data.preprocess``), kept here so that the inputs the benchmark
offers are made by the benchmark alone. Each dataset has its own
generative process, so reconstruction error under each autoencoder
separates them, and its own class structure. Every sample is flattened
or pooled to the matcher's 784 features. ``load`` splits each dataset
50/25/25 into the server's part (the autoencoders train on it) and two
client parts (requests draw their fingerprints from client A).
"""
from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import numpy as np

# name: (n_classes, raw shape, (largest, smallest) class percentage)
SPECS: Dict[str, Tuple[int, Tuple[int, ...], Tuple[float, float]]] = {
    "stl10": (10, (32, 32), (10.0, 10.0)),
    "mnist": (10, (28, 28), (11.35, 8.92)),
    "har": (6, (561,), (19.0, 14.0)),
    "reuters": (4, (2000,), (43.12, 8.14)),
    "nlos": (3, (28, 28), (33.33, 33.33)),
    "db": (3, (28, 28), (33.33, 33.33)),
}


def _class_sizes(n_classes, lc_sc, n):
    lc, sc = lc_sc
    fracs = np.linspace(sc, lc, n_classes)
    sizes = np.floor(fracs / fracs.sum() * n).astype(int)
    sizes[-1] += n - sizes.sum()
    return sizes


def _smooth2d(img, it=2):
    for _ in range(it):
        img = (img + np.roll(img, 1, -1) + np.roll(img, -1, -1)
               + np.roll(img, 1, -2) + np.roll(img, -1, -2)) / 5.0
    return img


def _norm01(x):
    ax = tuple(range(1, x.ndim))
    lo, hi = x.min(axis=ax, keepdims=True), x.max(axis=ax, keepdims=True)
    return (x - lo) / np.maximum(hi - lo, 1e-6)


def _mnist(rng, shape, sizes):
    protos = _smooth2d(rng.normal(size=(len(sizes),) + shape), 3)
    protos = (protos > np.quantile(protos, 0.8, axis=(1, 2),
                                   keepdims=True)).astype(np.float32)
    protos = _smooth2d(protos, 1)
    out = []
    for c, sz in enumerate(sizes):
        shift = rng.integers(-2, 3, size=(sz, 2))
        base = np.stack([np.roll(np.roll(protos[c], a, 0), b, 1)
                         for a, b in shift])
        out.append(np.clip(base + rng.normal(0, 0.15, size=base.shape), 0, 1))
    return out


def _stl10(rng, shape, sizes):
    H, W = shape
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    out = []
    for c, sz in enumerate(sizes):
        fx, fy = 0.3 + 0.25 * c, 0.2 + 0.15 * ((c * 3) % len(sizes))
        ph = rng.uniform(0, 2 * np.pi, size=(sz, 2, 1, 1))
        img = (np.sin(fx * xx + ph[:, 0]) * np.cos(fy * yy + ph[:, 1])
               + rng.normal(0, 0.4, size=(sz, H, W)))
        out.append(_norm01(img))
    return out


def _har(rng, shape, sizes):
    (D,) = shape
    t = np.linspace(0, 6 * np.pi, D, dtype=np.float32)
    out = []
    for c, sz in enumerate(sizes):
        f = 1.0 + 0.7 * c
        amp = rng.uniform(0.5, 1.5, size=(sz, 1))
        phase = rng.uniform(0, 2 * np.pi, size=(sz, 1))
        sig = (amp * np.sin(f * t + phase) + 0.3 * np.sin(2.3 * f * t
                                                         + 2 * phase)
               + rng.normal(0, 0.2, size=(sz, D)))
        out.append(_norm01(sig))
    return out


def _reuters(rng, shape, sizes):
    (V,) = shape
    zipf = 1.0 / np.arange(1, V + 1) ** 1.1
    out = []
    for c, sz in enumerate(sizes):
        topic = np.roll(zipf, 137 * c) * rng.gamma(2.0, 1.0, size=V)
        counts = rng.multinomial(200, topic / topic.sum(), size=sz)
        out.append(np.log1p(counts.astype(np.float32)))
    return [_norm01(np.concatenate(out))]


def _nlos(rng, shape, sizes):
    H, W = shape
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32) / H
    out = []
    for c, sz in enumerate(sizes):
        cx = rng.uniform(0.3, 0.7, size=(sz, 1, 1))
        cy = rng.uniform(0.3, 0.7, size=(sz, 1, 1))
        if c == 0:
            occ = np.exp(-((xx - cx) ** 2) / 0.01)
        elif c == 1:
            occ = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2)) / 0.02)
        else:
            occ = ((xx > cx) & (yy > cy)).astype(np.float32)
        img = _smooth2d(1.0 - 0.8 * occ
                        + rng.normal(0, 0.05, size=(sz, H, W)), 3)
        out.append(_norm01(img))
    return out


def _db(rng, shape, sizes):
    H, W = shape
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    rad = np.sqrt((xx - W / 2) ** 2 + (yy - H / 2) ** 2)
    field = (rad < 0.45 * W).astype(np.float32)
    out = []
    for c, sz in enumerate(sizes):
        img = np.repeat(field[None] * 0.6, sz, axis=0)
        for _ in range(2 + 4 * c):
            lx = rng.uniform(0.3 * W, 0.7 * W, size=(sz, 1, 1))
            ly = rng.uniform(0.3 * H, 0.7 * H, size=(sz, 1, 1))
            img += 0.35 * np.exp(-(((xx - lx) ** 2 + (yy - ly) ** 2)) / 3.0)
        img += rng.normal(0, 0.05, size=img.shape)
        out.append(_norm01(_smooth2d(img, 1)))
    return out


_GEN = {"mnist": _mnist, "stl10": _stl10, "har": _har,
        "reuters": _reuters, "nlos": _nlos, "db": _db}


def _to_784(x: np.ndarray) -> np.ndarray:
    """Images: area-weighted resize to 28x28; 1-D: adaptive average pool
    (or linear upsampling) to 784."""
    if x.ndim == 3:
        N, H, W = x.shape
        if (H, W) != (28, 28):
            ys, xs = np.linspace(0, H - 1, 28), np.linspace(0, W - 1, 28)
            yi = np.clip(ys.astype(int), 0, H - 2)
            xi = np.clip(xs.astype(int), 0, W - 2)
            fy = (ys - yi)[None, :, None]
            fx = (xs - xi)[None, None, :]
            x = ((1 - fy) * (1 - fx) * x[:, yi][:, :, xi]
                 + fy * (1 - fx) * x[:, yi + 1][:, :, xi]
                 + (1 - fy) * fx * x[:, yi][:, :, xi + 1]
                 + fy * fx * x[:, yi + 1][:, :, xi + 1])
        return x.reshape(N, -1).astype(np.float32)
    N, D = x.shape
    if D < 784:
        pos = np.linspace(0, D - 1, 784)
        lo = np.clip(pos.astype(int), 0, D - 2)
        f = pos - lo
        return ((1 - f) * x[:, lo] + f * x[:, lo + 1]).astype(np.float32)
    starts = (np.arange(784) * D) // 784
    ends = ((np.arange(784) + 1) * D + 783) // 784
    return np.stack([x[:, s:e].mean(axis=1) for s, e in zip(starts, ends)],
                    axis=1).astype(np.float32)


def generate(name: str, n: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(x (n, 784) float32, y (n,) int32), shuffled."""
    n_classes, shape, lc_sc = SPECS[name]
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % 10_000)
    sizes = _class_sizes(n_classes, lc_sc, n)
    x = np.concatenate(_GEN[name](rng, shape, sizes)).astype(np.float32)
    y = np.concatenate([np.full(s, c) for c, s in enumerate(sizes)])
    perm = np.random.default_rng(seed).permutation(n)
    return _to_784(x[perm]), y[perm].astype(np.int32)


def load(names: Sequence[str], n_per_dataset: int, seed: int
         ) -> Dict[str, Dict[str, Tuple[np.ndarray, np.ndarray]]]:
    """{name: {"server" | "client_a" | "client_b": (x784, y)}}."""
    out = {}
    for name in names:
        x, y = generate(name, n_per_dataset, seed)
        perm = np.random.default_rng(seed).permutation(n_per_dataset)
        s, a = n_per_dataset // 2, n_per_dataset // 4
        parts = {"server": perm[:s], "client_a": perm[s:s + a],
                 "client_b": perm[s + a:s + 2 * a]}
        out[name] = {k: (x[i], y[i]) for k, i in parts.items()}
    return out
