"""Each traffic mix is a pure function of its seed, and its damage rects
cover every pixel that changes."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.content import Scene

MIXES = sorted(p.stem for p in
               (Path(__file__).resolve().parents[2] / "benchmark" / "traffic").glob("*.json"))
SEED = 2**31 + 12345  # the driver's seeds exceed 32 signed bits


def _scene(mix, seed, w=320, h=180):
    root = Path(__file__).resolve().parents[2]
    return Scene(json.loads((root / "benchmark" / "traffic" / f"{mix}.json").read_text()),
                 w, h, seed)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_frames_any_order(mix):
    a, b = _scene(mix, SEED), _scene(mix, SEED)
    ks = [0, 1, 29, 30, 31, 59, 60 * 10**10 + 7]
    fa = [a.frame(k) for k in ks]
    fb = [b.frame(k) for k in reversed(ks)][::-1]
    for x, y in zip(fa, fb):
        assert x.shape == (180, 320, 4) and x.dtype == np.uint8
        assert np.array_equal(x, y)


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_frames(mix):
    assert not np.array_equal(_scene(mix, SEED).frame(5), _scene(mix, SEED + 1).frame(5))


@pytest.mark.parametrize("mix", MIXES)
def test_damage_covers_every_change(mix):
    sc = _scene(mix, SEED)
    for ka, kb in [(0, 1), (1, 3), (5, 6), (27, 28), (28, 29), (29, 30), (3, 40)]:
        diff = (sc.frame(ka) != sc.frame(kb)).any(-1)
        rects = sc.damage(ka, kb)
        if rects is None:
            continue
        covered = np.zeros_like(diff)
        for x, y, w, h in rects:
            covered[y:y + h, x:x + w] = True
        assert not (diff & ~covered).any(), (mix, ka, kb)
    assert sc.damage(7, 7) == [] and sc.damage(8, 7) is None


def test_every_mix_changes_every_frame():
    for mix in MIXES:
        sc = _scene(mix, SEED)
        assert all(not np.array_equal(sc.frame(k), sc.frame(k + 1)) for k in range(4))
