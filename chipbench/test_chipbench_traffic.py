"""Traffic is a pure function of the seed."""

import numpy as np
import pytest

from chipbench import cells, scene
from chipbench.conftest import cell

SEEDS = (0, 2**31 + 12345)


def sample(name, seed, frames=3, clients=3):
    _, cfg, mix = cell(name)
    out = []
    for s in scene.streams(cfg, mix, seed)[:clients]:
        for t in range(frames):
            f = s.frame(t)
            out.append((f, s.queries(t, f)))
    return out


def same(a, b) -> bool:
    for (fa, qa), (fb, qb) in zip(a, b):
        if not np.array_equal(fa, fb):
            return False
        for x, y in zip(qa, qb):
            for k in vars(x):
                if not np.array_equal(np.asarray(getattr(x, k), object)
                                      if k == "windows" else getattr(x, k),
                                      getattr(y, k)):
                    return False
    return True


@pytest.mark.parametrize("name", ["vga32.live", "hd32.archive",
                                  "vga32.archive"])
def test_deterministic_per_seed(name):
    a = sample(name, SEEDS[1])
    assert same(a, sample(name, SEEDS[1]))
    assert not same(a, sample(name, SEEDS[0]))


def test_frames_have_flat_areas_and_move():
    _, cfg, mix = cells.cell("vga32.live")
    s = scene.streams(cfg, mix, 7)[0]
    f0, f1 = s.frame(0), s.frame(1)
    # one value fills whole 128-pixel tile rows (the sky band)
    assert any(np.unique(f0[r]).size == 1 for r in range(cfg["height"]))
    changed = np.flatnonzero(np.any(f0 != f1, axis=1))
    assert 0 < changed.size < cfg["height"] * 0.35


def test_moving_camera_frames_are_all_new():
    _, cfg, mix = cell("vga32.archive")
    s = scene.streams(cfg, mix, 3)[0]
    f0, f1 = s.frame(0), s.frame(1)
    # every textured row moves with the pan: past the incremental gate
    assert np.mean(np.any(f0 != f1, axis=1)) > 0.35


def test_aerial_frames_have_one_bin_past_2_24_pixels():
    """Every full-size frame of the 8192x8192 aerial mix, wherever the pan
    crops it, has a flat area of one bin over 30% of its pixels: past
    2**24, where float32 counts only even integers."""
    _, cfg, mix = cell("paper8k128.sharded4")
    s = scene.streams(cfg, mix, 2**31 + 17)[1]
    for t in (0, 37):
        f = s.frame(t)
        assert f.shape == (8192, 8192)
        counts = np.bincount(scene.bin_ids(f, cfg["bins"],
                                           cfg["value_range"]).ravel())
        assert counts.max() >= 0.3 * 2**26 - 32 * 48 * 48 > 2**24


def test_sampling_is_drawn_from_the_seed():
    picks = [scene.sampled(5, n, 4) for n in range(400)]
    assert picks == [scene.sampled(5, n, 4) for n in range(400)]
    assert sum(picks) == 100
    phases = {next(n for n in range(64) if scene.sampled(seed, n, 64))
              for seed in range(40)}
    assert len(phases) > 10
