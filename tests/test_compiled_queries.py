"""Dense queries run as compiled programs, with strided slices for the
window corners.

On a dense H, ``sliding_window_histograms`` is one program
(``_dense_windows``) whose corner lattices are ``lax.slice`` calls with
strides, a likelihood map adds one scoring program (``_score``), and
``region_histogram`` is one program (``_dense_regions``) per rect count.
These tests check the lowering (no ``gather`` on the window path, one
strided slice of H where the stride divides the window's height, one
compile per rect count), that the programs are bit-exact against the
eager per-window gather, for a float32 H and an int32 H past 2**24, and
a NumPy four-corner sum, and the ``path`` and ``lattices`` attributes of
the engine's ``engine.query`` span.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distances
from repro.core import region_query as rq
from repro.kernels.ref import integral_histogram_ref

H_, W_, BINS = 24, 30, 8


def _h(rng, lead=()):
    n = int(np.prod(lead, dtype=np.int64))
    imgs = rng.integers(0, 256, (n, H_, W_), dtype=np.uint8)
    Hs = jnp.stack([integral_histogram_ref(jnp.asarray(im), BINS)
                    for im in imgs])
    return Hs.reshape(lead + (BINS, H_, W_))


def _slices_of_h(jaxpr):
    """The ``slice`` equations of the window program that read its H."""
    [program] = [e for e in jaxpr.eqns
                 if e.params.get("name") == "_dense_windows"]
    inner = program.params["jaxpr"].jaxpr
    return [e for e in inner.eqns
            if e.primitive.name == "slice" and e.invars[0] is inner.invars[0]]


def _primitives(jaxpr):
    """Every primitive name in ``jaxpr``, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _primitives(sub)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lead", [(), (3,)], ids=["frame", "stack"])
def test_dense_window_path_has_no_gather(rng, lead):
    H = _h(rng, lead)
    target = jnp.ones((BINS,), jnp.float32)
    windows = jax.make_jaxpr(
        lambda H: rq.sliding_window_histograms(H, (8, 10), 3))(H)
    lmap = jax.make_jaxpr(lambda H, t: rq.likelihood_map(
        H, t, (8, 10), distances.intersection, 3))(H, target)
    for jaxpr in (windows.jaxpr, lmap.jaxpr):
        prims = list(_primitives(jaxpr))
        assert "gather" not in prims
        # two row lattices, then four corner lattices along the columns
        assert prims.count("slice") == 6
        assert len(_slices_of_h(jaxpr)) == 2
    # a dense likelihood map is two programs: window histograms, then score
    assert [e.params["name"] for e in lmap.jaxpr.eqns] == [
        "_dense_windows", "_score"]
    # where the stride divides the window, both row lattices are one:
    # H is read by a single slice, strided along its row axis
    shared = jax.make_jaxpr(
        lambda H: rq.sliding_window_histograms(H, (8, 8), 2))(H).jaxpr
    assert "gather" not in list(_primitives(shared))
    [read] = _slices_of_h(shared)
    assert read.params["strides"] == (1,) * (H.ndim - 2) + (2, 1)


def test_dense_region_program_compiles_once_per_rect_count(rng):
    H = _h(rng)
    rects = np.array([[0, 0, 5, 5], [3, 4, 20, 29], [7, 0, 7, 0]], np.int32)
    rq.region_histogram(H, rects)
    n = rq._dense_regions._cache_size()
    moved = np.array([[1, 2, 9, 9], [0, 0, 23, 29], [5, 6, 6, 8]], np.int32)
    rq.region_histogram(H, moved)
    assert rq._dense_regions._cache_size() == n


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lead", [(), (2, 3)], ids=["frame", "two_axes"])
@pytest.mark.parametrize("window,stride", [
    ((5, 7), 3),        # odd stride
    ((6, 4), 2),        # even stride
    ((3, 4), 5),        # stride larger than the window
    ((1, 1), 1),        # corners on row and column 0
    ((1, 3), 2),        # corners on row 0
    ((24, 30), 1),      # window equal to the frame
    ((24, 30), 4),      # ... at a stride past the frame
    ((25, 30), 1),      # window taller than the frame: empty
    ((24, 31), 2),      # window wider than the frame: empty
    ((8, 8), 2),        # the stride divides both sides: one lattice each
    ((8, 12), 4),       # ... with rows and columns wh/s, ww/s apart
    ((4, 4), 4),        # the stride equal to the window
    ((8, 10), 4),       # the stride divides the height only
    ((9, 8), 4),        # the stride divides the width only
])
def test_compiled_windows_bit_exact_vs_gather(rng, lead, window, stride):
    H = _h(rng, lead)
    n_r = max((H_ - window[0]) // stride + 1, 0)
    n_c = max((W_ - window[1]) // stride + 1, 0)
    # the same frames with 2**24 + 1 more counts of every bin at pixel
    # (0, 0): every entry of the int32 H lies past float32's exact range
    for H in (H, H.astype(jnp.int32) + (1 << 24) + 1):
        got = rq.sliding_window_histograms(H, window, stride)
        want = rq.sliding_window_histograms(H, window, stride,
                                            impl="gather")
        assert got.shape == lead + (n_r, n_c, BINS)
        assert got.dtype == jnp.float32
        # the int32 field is summed exactly, then rounded once to fp32
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want).astype(np.float32))


def _numpy_regions(Hn, rects):
    """Per-rect four-corner sum in NumPy, virtual row/column -1 as 0."""
    def at(r, c):
        if r < 0 or c < 0:
            return np.zeros(Hn.shape[:-2], Hn.dtype)
        return Hn[..., r, c]

    return np.stack([
        at(r1, c1) - at(r0 - 1, c1) - at(r1, c0 - 1) + at(r0 - 1, c0 - 1)
        for r0, c0, r1, c1 in rects
    ], axis=-2)


@pytest.mark.parametrize("n_rects", [1, 9, 36])
def test_compiled_regions_bit_exact_vs_numpy(rng, n_rects):
    H = _h(rng, (3,))
    r0 = rng.integers(0, H_, n_rects)
    c0 = rng.integers(0, W_, n_rects)
    r1 = rng.integers(r0, H_)
    c1 = rng.integers(c0, W_)
    rects = np.stack([r0, c0, r1, c1], -1).astype(np.int32)
    rects[0, :2] = 0                          # touches row 0 and column 0
    if n_rects > 1:
        rects[1, 0] = 0                       # touches row 0
        rects[-1, 1] = 0                      # touches column 0
    got = rq.region_histogram(H, rects)
    assert got.shape == (3, n_rects, BINS)
    np.testing.assert_array_equal(np.asarray(got),
                                  _numpy_regions(np.asarray(H), rects))


# ---------------------------------------------------------------------------
# the engine.query span says which path answered, and how many row
# lattices the window program took
# ---------------------------------------------------------------------------
@pytest.fixture
def query_spans(monkeypatch):
    """The stats of every ``engine.query`` span the engine opens."""
    from repro.core import engine as engine_mod

    spans = []

    class Recorder:
        def __init__(self, name, **stats):
            if name == "engine.query":
                spans.append(stats)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **stats):
            pass

    monkeypatch.setattr(engine_mod, "TraceAnnotation", Recorder)
    return spans


@pytest.mark.parametrize("budget,path", [(None, "compiled"),
                                         (4 * BINS * W_ * 8, "rows")],
                         ids=["dense", "banded"])
def test_engine_query_span_names_its_path(rng, query_spans, budget, path):
    from repro.core import engine as engine_mod

    eng = engine_mod.HistogramEngine(BINS, backend="jnp",
                                     memory_budget_bytes=budget)
    frame = rng.integers(0, 256, (H_, W_), dtype=np.uint8)
    target = np.ones((BINS,), np.float32)
    out = eng.run(frame, [engine_mod.LikelihoodQuery(target, (4, 4)),
                          engine_mod.RegionQuery([[0, 0, 9, 9]])])
    assert out.plan.representation == ("dense" if budget is None
                                       else "banded")
    assert [q["path"] for q in query_spans] == [path, path]
    # only the window program's answers count its row lattices
    assert [q.get("lattices") for q in query_spans] == (
        [1, None] if path == "compiled" else [None, None])


@pytest.mark.parametrize("kind,window,stride,lattices", [
    ("LikelihoodQuery", (48, 48), 4, 1),
    ("LikelihoodQuery", (8, 10), 3, 2),
    ("SlidingWindowQuery", (8, 10), 4, 1),
    ("MultiScaleQuery", ((16, 16), (9, 9)), 4, 2),
])
def test_engine_query_span_counts_row_lattices(rng, query_spans, kind,
                                               window, stride, lattices):
    from repro.core import engine as engine_mod

    target = np.ones((BINS,), np.float32)
    query = {
        "LikelihoodQuery": lambda: engine_mod.LikelihoodQuery(
            target, window, stride=stride),
        "SlidingWindowQuery": lambda: engine_mod.SlidingWindowQuery(
            window, stride),
        "MultiScaleQuery": lambda: engine_mod.MultiScaleQuery(
            target, window, stride=stride),
    }[kind]()
    eng = engine_mod.HistogramEngine(BINS, backend="jnp")
    frame = rng.integers(0, 256, (64, 96), dtype=np.uint8)
    # fragments on every other row: too many corner rows to fuse, so H
    # is dense and the window program answers
    rows = engine_mod.RegionQuery([[r, 0, r, 9] for r in range(0, 64, 2)])
    out = eng.run(frame, [query, rows])
    assert out.plan.representation == "dense"
    window_span, region_span = query_spans
    assert (window_span["kind"], window_span["path"]) == (kind, "compiled")
    assert window_span["lattices"] == lattices
    assert "lattices" not in region_span
