"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program and takes nothing it made:

- decoding: FFmpeg's H.264 decoder (inside OpenCV), fed the AUs exactly as
  the client received them, de-framed by the client's own header parse;
- the picture each AU should hold: the content frame its capture returned,
  composed again from the seed by the benchmark's generator, converted to
  luma by the BT.601 limited-range formula written out below.

Numbers compared, each against a limit in ``benchmark/limits/<cell>.json``:

- ``decode_errors``: FFmpeg error lines, plus AUs the decoder returned no
  picture for, from the IDR the window's first AU depends on through the
  last AU received in the window. Limit 0.
- ``lost_aus``: window captures the encoder completed whose AU never
  reached the client. Limit 0.
- ``worst_mb_mse``: over a seeded sample of the window's AUs, the largest
  luma mean squared error of a 16x16 macroblock of a decoded picture
  against its reference picture.
- ``worst_frame_mse``: the same over whole pictures (a frame's mean
  dilutes local faults; on the chip it did not separate sound runs from
  the control by three times, so no cell is judged on it yet).

A cell's limits file lists the numbers it is judged on; their limits come
from readings of sound runs and of the control (PERF.md).
"""

from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

# the largest luma MSE a picture can have: what a missing picture reads
NO_PICTURE = 255.0 ** 2


def luma_bt601(bgrx: np.ndarray) -> np.ndarray:
    """BT.601 limited-range luma of a BGRx frame, float64."""
    b = bgrx[..., 0].astype(np.float64)
    g = bgrx[..., 1].astype(np.float64)
    r = bgrx[..., 2].astype(np.float64)
    return 16.0 + (65.481 * r + 128.553 * g + 24.966 * b) / 255.0


def mse(decoded: np.ndarray, reference: np.ndarray) -> tuple[float, float]:
    """(frame, largest 16x16 block) mean squared error; edge blocks are
    partial."""
    h, w = reference.shape
    err = (decoded[:h, :w].astype(np.float64) - reference) ** 2
    rows = np.add.reduceat(err, np.arange(0, h, 16), axis=0)
    blocks = np.add.reduceat(rows, np.arange(0, w, 16), axis=1)
    bh = np.minimum(16, h - np.arange(0, h, 16))
    bw = np.minimum(16, w - np.arange(0, w, 16))
    return float(err.mean()), float((blocks / np.outer(bh, bw)).max())


class _CaptureFd2:
    """FFmpeg inside OpenCV writes decode errors to fd 2."""

    def __enter__(self):
        sys.stderr.flush()
        self._tmp = tempfile.TemporaryFile()
        self._saved = os.dup(2)
        os.dup2(self._tmp.fileno(), 2)
        return self

    def __exit__(self, *exc):
        os.dup2(self._saved, 2)
        os.close(self._saved)
        self._tmp.seek(0)
        self.text = self._tmp.read().decode(errors="replace")
        self._tmp.close()


def decode_lumas(aus: list, keep: set, workdir: str) -> tuple[dict, int, int]:
    """Decode an Annex-B AU list; -> ({index: luma} for indices in keep,
    pictures decoded, decoder error lines)."""
    import cv2

    path = os.path.join(workdir, "stream.h264")
    with open(path, "wb") as f:
        for au in aus:
            f.write(au)
    kept: dict = {}
    n = 0
    with _CaptureFd2() as err:
        cap = cv2.VideoCapture(path, cv2.CAP_FFMPEG, [cv2.CAP_PROP_CONVERT_RGB, 0])
        while True:
            ok, pic = cap.read()
            if not ok:
                break
            if n in keep:
                kept[n] = pic if pic.ndim == 2 else pic[..., 0]
            n += 1
        cap.release()
    os.unlink(path)
    errors = [ln for ln in err.text.splitlines() if "[h264 @" in ln]
    return kept, n, len(errors)


def compare(received: list, window_idx: list, credit: list, scene, lost: int,
            sample: int, seed: int, workdir: str) -> dict:
    """``received``: every AU the client got, in order, as (payload, idr);
    ``window_idx``: indices into it of the window's AUs, in order;
    ``credit``: per window AU, the content index it is compared with.
    -> {name: value} of the numbers compared."""
    if not window_idx:
        return {"decode_errors": 1, "lost_aus": lost,
                "worst_frame_mse": NO_PICTURE, "worst_mb_mse": NO_PICTURE}
    first = window_idx[0]
    start = max((i for i in range(first + 1) if received[i][1]), default=0)
    last = window_idx[-1]
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 7])
    picks = set(rng.choice(len(window_idx), min(sample, len(window_idx)),
                           replace=False).tolist()) | {0, len(window_idx) - 1}
    keep = {window_idx[j] - start for j in picks}
    lumas, n, errors = decode_lumas([p for p, _ in received[start:last + 1]],
                                    keep, workdir)
    missing = (last + 1 - start) - n
    worst_frame = worst_mb = 0.0
    for j in sorted(picks):
        pic = lumas.get(window_idx[j] - start)
        f, b = (NO_PICTURE, NO_PICTURE) if pic is None else mse(
            pic, luma_bt601(scene.frame(credit[j])))
        worst_frame, worst_mb = max(worst_frame, f), max(worst_mb, b)
    return {"decode_errors": errors + max(0, missing), "lost_aus": lost,
            "worst_frame_mse": worst_frame, "worst_mb_mse": worst_mb}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Judge the numbers ``limits`` names; -> (correct, {name: {"value": v,
    "limit": l}})."""
    out = {name: {"value": values[name], "limit": lim} for name, lim in limits.items()}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out
