"""The one general content generator: a traffic mix's layers, frame by frame.

A mix (``benchmark/traffic/<name>.json``) lists layers that are drawn in
order onto each content frame. Content frame ``k`` is a pure function of
(mix, resolution, seed, k): every random draw that belongs to frame ``k``
comes from ``default_rng([seed, layer, k])``, so a frame can be composed at
capture time, out of order, and again by the reference after the window.
Strip chunks are built as they come on screen and dropped after.

Positions and sizes in a mix are in 1920x1080 reference pixels and scale
with the configuration's resolution; speeds (``step_px``) are in output
pixels per content frame.

``damage(ka, kb)`` returns rects ``(x, y, w, h)`` that cover every pixel
that may differ between content frames ``ka`` and ``kb`` (``None`` for
the whole frame), the superset an XDamage-armed X11 capture reports.
"""

from __future__ import annotations

import numpy as np

REF_W, REF_H = 1920, 1080
# two layer-local draws never share a stream: the layer's index is part
# of every seed sequence
_U64 = (1 << 64) - 1


def _rng(seed: int, layer: int, k: int) -> np.random.Generator:
    return np.random.default_rng([seed & _U64, layer, k & _U64])


def _blocks(rng, h: int, w: int, block: int, lo: int, hi: int,
            grain: int = 0) -> np.ndarray:
    """(h, w, 4) BGRx of random ``block``-pixel squares, plus optional
    per-pixel grain of amplitude ``grain``."""
    cells = rng.integers(lo, hi, (-(-h // block), -(-w // block), 4), np.uint8)
    img = np.repeat(np.repeat(cells, block, 0), block, 1)[:h, :w]
    if grain:
        g = rng.integers(-grain, grain + 1, (h, w, 1), np.int16)
        img = np.clip(img.astype(np.int16) + g, 0, 255).astype(np.uint8)
    img[..., 3] = 0
    return np.ascontiguousarray(img)


class _Layer:
    def draw(self, out: np.ndarray, k: int) -> None:
        raise NotImplementedError

    def rects(self, ka: int, kb: int) -> list | None:
        """Rects that may change between frames ka < kb; None = all."""
        return []


class _Band(_Layer):
    """A band of fresh noise rows, new on every frame."""

    def __init__(self, p, sc, seed, li):
        self.seed, self.li = seed, li
        self.x, self.y, self.w, self.h = sc.rect(p["rect"])

    def draw(self, out, k):
        out[self.y:self.y + self.h, self.x:self.x + self.w] = _rng(
            self.seed, self.li, k).integers(0, 256, (self.h, self.w, 4), np.uint8)

    def rects(self, ka, kb):
        return [(self.x, self.y, self.w, self.h)]


class _Sprite(_Layer):
    """A solid square moving ``step_px`` right per frame along row y."""

    def __init__(self, p, sc, seed, li):
        self.x0, self.y, self.size, _ = sc.rect([0, p["y"], p["size"], 0])
        self.step, self.bgrx = int(p["step_px"]), p["bgrx"]
        self.span = sc.width - self.size

    def xpos(self, k: int) -> int:
        return (k * self.step) % self.span

    def draw(self, out, k):
        x = self.xpos(k)
        out[self.y:self.y + self.size, x:x + self.size] = self.bgrx

    def rects(self, ka, kb):
        return [(self.xpos(ka), self.y, self.size, self.size),
                (self.xpos(kb), self.y, self.size, self.size)]


class _Slide(_Layer):
    """A region sliding over an endless seeded strip: frame k shows strip
    columns [k*step_px, k*step_px + w), so the strip never repeats. Strip
    columns come in ``step_px``-wide chunks of random ``block``-pixel
    squares with ``grain`` (block 1, grain 0: noise), chunk j drawn from
    its own stream and cached while it is on screen."""

    def __init__(self, p, sc, seed, li):
        self.seed, self.li = seed, li
        self.x, self.y, self.w, self.h = sc.rect(p["rect"])
        self.step = int(p["step_px"])
        self.texture = (int(p.get("block", 1)), int(p.get("lo", 0)),
                        int(p.get("hi", 256)), int(p.get("grain", 0)))
        self.n = -(-self.w // self.step) + 1
        self._chunks: dict[int, np.ndarray] = {}

    def chunk(self, j: int) -> np.ndarray:
        c = self._chunks.get(j)
        if c is None:
            block, lo, hi, grain = self.texture
            c = _blocks(_rng(self.seed, self.li, j), self.h, self.step,
                        block, lo, hi, grain)
            self._chunks[j] = c
            if len(self._chunks) > 4 * self.n:
                for old in sorted(self._chunks)[:len(self._chunks) - 2 * self.n]:
                    del self._chunks[old]
        return c

    def draw(self, out, k):
        region = np.concatenate([self.chunk(k + j) for j in range(self.n)], 1)
        out[self.y:self.y + self.h, self.x:self.x + self.w] = region[:, :self.w]

    def rects(self, ka, kb):
        return [(self.x, self.y, self.w, self.h)]


class Scene:
    """Content frames of one mix at one resolution and seed."""

    def __init__(self, mix: dict, width: int, height: int, seed: int):
        self.width, self.height, self.seed = width, height, seed
        self.layers: list[_Layer] = [_KINDS[p["kind"]](p, self, seed, li)
                                     for li, p in enumerate(mix["layers"])]

    def rect(self, r) -> tuple[int, int, int, int]:
        """Reference-pixel rect -> output pixels (clipped)."""
        sx, sy = self.width / REF_W, self.height / REF_H
        x, y = int(r[0] * sx), int(r[1] * sy)
        w, h = int(r[2] * sx), int(r[3] * sy)
        w, h = min(w, self.width - x), min(h, self.height - y)
        return x, y, w, h

    def frame(self, k: int) -> np.ndarray:
        """Content frame k as a fresh (H, W, 4) BGRx uint8 array."""
        out = np.empty((self.height, self.width, 4), np.uint8)
        for layer in self.layers:
            layer.draw(out, k)
        return out

    def damage(self, ka: int, kb: int) -> list | None:
        """Rects covering every change between frames ka and kb; None =
        the whole frame (also for a jump backwards)."""
        if kb == ka:
            return []
        if kb < ka:
            return None
        rects: list = []
        for layer in self.layers:
            r = layer.rects(ka, kb)
            if r is None:
                return None
            rects.extend(r)
        return rects


_KINDS = {"band": _Band, "sprite": _Sprite, "slide": _Slide}
