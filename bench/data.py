"""Seeded input generators of the benchmark.

Copies of the program's synthetic frame generators
(``repro.data.synthetic.mnist_like`` / ``road_like``) and of the lognormal
per-request intensity skew of ``benchmarks/serve_load._skewed_frames``, kept
here so that a change to the program cannot change the benchmark's inputs.
Every function draws only from the ``numpy`` generator it is given.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

# 7-segment-like strokes on a 20x12 canvas, per digit
_SEGS = {
    0: "abcdef", 1: "bc", 2: "abged", 3: "abgcd", 4: "fgbc",
    5: "afgcd", 6: "afgedc", 7: "abc", 8: "abcdefg", 9: "abgfcd",
}
_SEG_COORDS = {  # (y0, x0, y1, x1) line endpoints
    "a": (1, 2, 1, 9), "b": (1, 9, 9, 9), "c": (9, 9, 17, 9),
    "d": (17, 2, 17, 9), "e": (9, 2, 17, 2), "f": (1, 2, 9, 2),
    "g": (9, 2, 9, 9),
}


def _render_digit(digit: int, rng: np.random.Generator, h: int,
                  w: int) -> np.ndarray:
    img = np.zeros((h, w), np.float32)
    oy, ox = rng.integers(2, 8), rng.integers(4, 12)
    thick = rng.integers(1, 3)
    for seg in _SEGS[digit]:
        y0, x0, y1, x1 = _SEG_COORDS[seg]
        n = max(abs(y1 - y0), abs(x1 - x0)) + 1
        ys = np.linspace(y0, y1, n).astype(int) + oy
        xs = np.linspace(x0, x1, n).astype(int) + ox
        for t in range(int(thick)):
            img[np.clip(ys + t, 0, h - 1), np.clip(xs, 0, w - 1)] = 1.0
            img[np.clip(ys, 0, h - 1), np.clip(xs + t, 0, w - 1)] = 1.0
    img += rng.normal(0, 0.08, img.shape).astype(np.float32)
    return np.clip(img, 0.0, 1.0)


def digits(rng: np.random.Generator, n: int, hw=(28, 28)
           ) -> Tuple[np.ndarray, np.ndarray]:
    """(n, H, W, 1) digit frames in [0, 1] and (n,) int32 labels."""
    labels = rng.integers(0, 10, n)
    imgs = np.stack([_render_digit(int(d), rng, *hw) for d in labels])
    return imgs[..., None], labels.astype(np.int32)


def skewed_digits(rng: np.random.Generator, n: int, hw=(28, 28),
                  mu: float = -0.5, sigma: float = 1.2) -> np.ndarray:
    """Digit frames scaled by a lognormal(mu, sigma) intensity per frame and
    clipped to [0, 1]: per-request spike workloads spread over orders of
    magnitude."""
    imgs, _ = digits(rng, n, hw)
    scale = rng.lognormal(mu, sigma, (n, 1, 1, 1))
    return np.clip(imgs * scale, 0.0, 1.0).astype(np.float32)


def roads(rng: np.random.Generator, n: int, hw=(80, 160), channels: int = 3
          ) -> np.ndarray:
    """(n, H, W, C) road-scene frames: a bright perspective trapezoid (the
    lane) on noise."""
    h, w = hw
    frames = rng.uniform(0.0, 0.35, (n, h, w, channels)).astype(np.float32)
    for i in range(n):
        cx = rng.uniform(0.35, 0.65) * w
        top_w = rng.uniform(0.05, 0.15) * w
        bot_w = rng.uniform(0.45, 0.8) * w
        horizon = int(rng.uniform(0.25, 0.45) * h)
        for y in range(horizon, h):
            frac = (y - horizon) / max(1, h - horizon)
            half = 0.5 * (top_w + frac * (bot_w - top_w))
            x0, x1 = int(max(0, cx - half)), int(min(w, cx + half))
            frames[i, y, x0:x1, :] += 0.4
    return np.clip(frames, 0.0, 1.0)


FRAMES = {"digits": lambda rng, n, cfg, **kw: digits(
              rng, n, tuple(cfg["input_hw"]))[0],
          "skewed_digits": lambda rng, n, cfg, **kw: skewed_digits(
              rng, n, tuple(cfg["input_hw"]), **kw),
          "roads": lambda rng, n, cfg, **kw: roads(
              rng, n, tuple(cfg["input_hw"]), cfg["input_channels"])}


def frames(kind: str, rng: np.random.Generator, n: int, cfg: dict,
           **kw) -> np.ndarray:
    """``n`` frames of the named kind at the configuration's input shape."""
    if kind not in FRAMES:
        raise ValueError(f"unknown frame kind {kind!r}; have {sorted(FRAMES)}")
    return FRAMES[kind](rng, n, cfg, **kw)
