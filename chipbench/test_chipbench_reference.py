"""The corner reference gives what the whole-H reference gives.

``reference.corners`` computes P only at the corners the queries read;
every answer read from it has to equal, bit for bit, the answer read
from the whole padded H (``padded(integral_histogram(...))``), in every
precision the check holds H in."""

import ml_dtypes
import numpy as np
import pytest

from chipbench import cells, check, reference as ref, scene
from chipbench.conftest import LATER, small

DTYPES = [np.int32, np.float32, ml_dtypes.bfloat16]


def frame_of(kind: str, seed: int, h: int, w: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    f = rng.integers(0, 256, (h, w), dtype=np.uint8)
    if kind == "flat":              # one value but for one small block
        f[:] = rng.integers(0, 256)
        f[h // 3:h // 3 + 4, w // 2:w // 2 + 5] = rng.integers(0, 256)
    return f


def rects_of(rng, h: int, w: int, n: int = 12) -> np.ndarray:
    """Random inclusive rects, and rects on every border of the frame."""
    r = np.sort(rng.integers(0, h, (n, 2)), axis=1)
    c = np.sort(rng.integers(0, w, (n, 2)), axis=1)
    border = [[0, 0, h - 1, w - 1], [0, 0, 0, 0], [h - 1, w - 1, h - 1, w - 1],
              [0, w - 3, 2, w - 1], [h - 1, 0, h - 1, w - 1]]
    return np.concatenate([np.stack([r[:, 0], c[:, 0], r[:, 1], c[:, 1]], 1),
                           border]).astype(np.int64)


# (window, stride): strides that divide the window and strides that
# do not, windows of the whole frame and of one pixel
WINDOWS = [((8, 8), 2), ((7, 5), 3), ((9, 4), 4), ((1, 1), 1), ((37, 45), 5)]


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("kind", ["random", "flat"])
@pytest.mark.parametrize("seed", [0, 1, 2**31 + 9])
def test_corner_answers_equal_the_whole_h_answers(dtype, kind, seed):
    h, w, bins = 37, 45, 8
    f = frame_of(kind, seed, h, w)
    rng = np.random.default_rng(seed)
    P = ref.padded(ref.integral_histogram(f, bins, 256, dtype))
    rects = rects_of(rng, h, w)
    target = rng.random(bins)
    rows = [rects[:, 0], rects[:, 2] + 1]
    cols = [rects[:, 1], rects[:, 3] + 1]
    for (wh, ww), s in WINDOWS:
        rows.append(ref.window_lattice(h, wh, s))
        cols.append(ref.window_lattice(w, ww, s))
    C = ref.corners(f, bins, 256, np.concatenate(rows), np.concatenate(cols),
                    dtype)
    want, got = ref.regions(P, rects), ref.regions(C, rects)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    for window, s in WINDOWS:
        np.testing.assert_array_equal(ref.windows(C, window, s),
                                      ref.windows(P, window, s))
        np.testing.assert_array_equal(ref.likelihood(C, target, window, s),
                                      ref.likelihood(P, target, window, s))
    scales = [w for w, s in WINDOWS if s == 4] + [(5, 4), (13, 8)]
    maps_c, best_c = ref.multiscale(C, target, scales, 4)
    maps_p, best_p = ref.multiscale(P, target, scales, 4)
    assert best_c == best_p
    for a, b in zip(maps_c, maps_p):
        np.testing.assert_array_equal(a, b)


def test_a_corner_not_computed_is_an_error():
    f = frame_of("random", 3, 20, 30)
    C = ref.corners(f, 4, 256, [0, 10], [0, 5, 30])
    assert C.shape == (4, 21, 31)
    np.testing.assert_array_equal(
        C[:, np.array([10]), np.array([30])],
        ref.padded(ref.integral_histogram(f, 4, 256))[:, [10], [30]])
    with pytest.raises(KeyError):
        C[:, np.array([11]), np.array([30])]
    with pytest.raises(ValueError):
        ref.corners(f, 4, 256, [21], [0])


@pytest.mark.parametrize("name", ["vga32.live", "hd32.archive"]
                         + [c["name"] for c in LATER])
def test_check_reads_every_corner_the_queries_need(name):
    """``check.compare`` of each cell's own frames and queries, answered
    by the whole-H reference, reads 0 everywhere: the corners it computes
    are the ones the queries read."""
    _, cfg, mix, _ = small(name)
    samples = []
    for client, s in enumerate(scene.streams(cfg, mix, 2**31 + 3)[:2]):
        for t in range(2):
            frame = s.frame(t)
            queries = s.queries(t, frame)
            P = ref.padded(ref.integral_histogram(frame, cfg["bins"],
                                                  cfg["value_range"]))
            answers = []
            for q in queries:
                kind = type(q).__name__
                if kind == "RegionQuery":
                    answers.append(ref.regions(P, q.rects).astype(np.float32))
                elif kind == "LikelihoodQuery":
                    answers.append(ref.likelihood(P, q.target, q.window,
                                                  q.stride))
                else:
                    maps, best = ref.multiscale(P, q.target, q.windows,
                                                q.stride)
                    k = int(np.argmax([m.max() for m in maps]))
                    i = int(np.argmax(maps[k]))
                    r0 = i // maps[k].shape[1] * q.stride
                    c0 = i % maps[k].shape[1] * q.stride
                    wh, ww = q.windows[k]
                    answers.append(([r0, c0, r0 + wh - 1, c0 + ww - 1], best,
                                    maps))
            samples.append(check.Sample(client, t, frame, queries, answers))
    assert check.compare(samples, cfg) == {
        "count_mismatch": 0, "lik_gap": 0.0, "ms_gap": 0.0, "checked": 4}


def test_past_2_24_pixels_int_is_exact_and_float32_miscounts():
    """A 4100x4100 frame (past 2**24 pixels) at 2 bins, one bin flat over
    all but one block: the reference counts it exactly in int64 (held in
    int32), and the float32 control miscounts regions near the corner."""
    from repro.core.engine import RegionQuery

    h = w = 4100
    f = np.full((h, w), 200, np.uint8)            # bin 1
    f[100:140, 200:260] = 3                       # bin 0: 40 x 60 pixels
    cfg = {"bins": 2, "value_range": 256, "height": h, "width": w}
    assert check.control_dtype(cfg) is np.float32
    rects = np.array([[0, 0, h - 1, w - 1], [h - 3, w - 3, h - 1, w - 1],
                      [100, 200, 139, 259], [h - 9, 0, h - 1, w - 1]])
    C = ref.corners(f, 2, 256, [0, 100, 140, h - 9, h - 3, h],
                    [0, 200, 260, w - 3, w])
    exact = ref.regions(C, rects)
    assert exact[0].tolist() == [40 * 60, h * w - 40 * 60]
    assert h * w - 40 * 60 > 2**24
    assert exact[1].tolist() == [0, 9]
    assert exact[2].tolist() == [40 * 60, 0]
    assert exact[3].tolist() == [0, 9 * w]
    sample = check.Sample(0, 0, f, [RegionQuery(rects)],
                          [exact.astype(np.float64)])
    assert check.compare([sample], cfg)["count_mismatch"] == 0
    assert check.control([sample], cfg, np.float32)["count_mismatch"] > 0
    assert check.control([sample], cfg, np.int32)["count_mismatch"] == 0


def test_unknown_query_has_no_reference():
    f = frame_of("random", 4, 8, 8)
    with pytest.raises(TypeError):
        check.grid(f, [object()], {"bins": 2, "value_range": 256}, np.int32)


@pytest.mark.parametrize("shape,dtype", [
    ("vga32", ml_dtypes.bfloat16), ("hd32", ml_dtypes.bfloat16),
    ("paper8k128", np.float32), ((4096, 4096), ml_dtypes.bfloat16),
    ((4096, 4097), np.float32)], ids=str)
def test_control_precision_comes_from_the_configuration(shape, dtype):
    """The control holds H one precision below the counts: bfloat16 where
    float32 counts a frame exactly (2**24 pixels or fewer), else float32."""
    if isinstance(shape, str):
        cfg = cells.config(shape)
    else:
        cfg = {"height": shape[0], "width": shape[1]}
    assert check.control_dtype(cfg) is dtype
