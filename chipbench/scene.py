"""The one traffic generator: frames and queries from a mix's parameters.

A traffic mix (``chipbench/traffic/<mix>.json``) is data only; this
module turns it, a configuration and ``--seed`` into per-client streams.
Every frame and query is a pure function of ``(seed, client, t)``, so a
run and the correctness check after it regenerate the same inputs.

Cameras:

* ``"fixed"``: a static background with large flat areas (sky, walls,
  road), bit-identical from frame to frame as the skip blocks of decoded
  H.264 are.  Objects move horizontally along lanes whose rows come from
  the mix's fixed layout table (``layouts``); the run's seed only chooses
  which camera gets which layout, so every seed exercises the same set of
  dirty-row bands (and so the same compiled shapes) in another order.
  A small share of frames are scene changes, redrawn whole.
* ``"moving"``: an archived clip from a panning camera.  Each frame is a
  crop of a larger background at a new offset with objects pasted in, so
  every frame is new and has no predecessor.

A mix's ``flat`` (``{"share": s}``) lays one more flat area over each
background: rows of one value across the whole width, at least ``s`` of
every frame's rows wherever the pan crops it.

Queries per frame come from the mix's ``queries`` list: ``likelihood``
(whole-frame sliding-window likelihood against a target histogram),
``fragments`` (a ``RegionQuery`` of a 3x3 grid of tracker fragments per
object, moving with the object and jittered as a tracker's estimate is)
and ``multiscale`` (a ``MultiScaleQuery``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

_SCENE_HORIZON = 1 << 15    # frames per camera before the change schedule repeats


def rng_for(*key) -> np.random.Generator:
    return np.random.default_rng([int(k) & 0xFFFFFFFF for k in key])


def bin_ids(pixels: np.ndarray, bins: int, value_range: int) -> np.ndarray:
    return (pixels.astype(np.int64) * bins) // value_range


def histogram(pixels: np.ndarray, bins: int, value_range: int) -> np.ndarray:
    """Counts of ``pixels`` per bin, float32 (the served target type)."""
    return np.bincount(bin_ids(pixels, bins, value_range).ravel(),
                       minlength=bins).astype(np.float32)


def background(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Blocky texture with noise, then flat sky, road and wall areas:
    about half of the pixels lie in regions of one value (one bin)."""
    coarse = rng.integers(0, 256, (h // 16 + 1, w // 16 + 1), dtype=np.int16)
    img = np.repeat(np.repeat(coarse, 16, axis=0), 16, axis=1)[:h, :w]
    img = img + rng.integers(-12, 13, (h, w), dtype=np.int16)
    img = np.clip(img, 0, 255).astype(np.uint8)
    sky = int(h * rng.uniform(0.15, 0.3))
    img[:sky] = rng.integers(0, 256)
    road = int(h * rng.uniform(0.1, 0.2))
    img[h - road:] = rng.integers(0, 256)
    for _ in range(int(rng.integers(2, 6))):
        rh = int(rng.integers(h // 8, h // 3))
        rw = int(rng.integers(w // 8, w // 3))
        r0 = int(rng.integers(sky, max(sky + 1, h - road - rh)))
        c0 = int(rng.integers(0, w - rw))
        img[r0:r0 + rh, c0:c0 + rw] = rng.integers(0, 256)
    return img


def sprite(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """An object: two flat halves and a textured band between them."""
    s = np.empty((h, w), np.uint8)
    s[: h // 2] = rng.integers(0, 256)
    s[h // 2:] = rng.integers(0, 256)
    b0, b1 = h // 3, h // 3 + max(1, h // 4)
    s[b0:b1] = rng.integers(0, 256, (b1 - b0, w), dtype=np.uint8)
    return s


def paste(frame: np.ndarray, spr: np.ndarray, top: int, left: int) -> None:
    """Paste ``spr`` with its top-left corner at (top, left), clipped."""
    h, w = frame.shape
    sh, sw = spr.shape
    c0, c1 = max(left, 0), min(left + sw, w)
    r0, r1 = max(top, 0), min(top + sh, h)
    if c0 < c1 and r0 < r1:
        frame[r0:r1, c0:c1] = spr[r0 - top:r1 - top, c0 - left:c1 - left]


@dataclasses.dataclass(frozen=True)
class Obj:
    """One moving object: fixed rows, horizontal motion."""

    top: int
    h: int
    w: int
    speed: int          # px per frame, signed


def layout_table(mix: dict, height: int) -> list[list[tuple[int, int, int]]]:
    """The mix's fixed layouts: per layout a list of (top, h, w) objects
    on one lane, or on two lanes for the first ``two_lane_share``."""
    spec = mix["layouts"]
    lo, hi = mix["objects"]["size"]
    n_lo, n_hi = mix["objects"]["count"]
    count = spec["count"]
    two = int(round(count * spec.get("two_lane_share", 0.0)))
    table = []
    for i in range(count):
        rng = rng_for(spec["seed"], i)
        n = int(rng.integers(n_lo, n_hi + 1))
        lanes = 2 if i < two and n > 1 else 1
        objs = []
        for lane in range(lanes):
            k = n // lanes + (1 if lane < n % lanes else 0)
            sizes = [(int(rng.integers(lo, hi + 1)), int(rng.integers(lo, hi + 1)))
                     for _ in range(k)]
            lane_h = max(sh for sh, _ in sizes) + int(rng.integers(0, 9))
            lane_top = int(rng.integers(0, height - lane_h + 1))
            for sh, sw in sizes:
                objs.append((lane_top + int(rng.integers(0, lane_h - sh + 1)),
                             sh, sw))
        table.append(objs)
    return table


class Stream:
    """One client's frames and queries (a camera or an archived clip)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, client: int,
                 layout=None):
        self.h, self.w = cfg["height"], cfg["width"]
        self.bins, self.vr = cfg["bins"], cfg["value_range"]
        self.mix, self.seed, self.client = mix, seed, client
        self.fixed = mix["camera"] == "fixed"
        rng = rng_for(seed, client, 1)
        lo, hi = mix["objects"]["speed"]
        if self.fixed:
            rows = layout
        else:
            n_lo, n_hi = mix["objects"]["count"]
            s_lo, s_hi = mix["objects"]["size"]
            rows = []
            for _ in range(int(rng.integers(n_lo, n_hi + 1))):
                oh = int(rng.integers(s_lo, s_hi + 1))
                rows.append((int(rng.integers(0, self.h - oh + 1)), oh,
                             int(rng.integers(s_lo, s_hi + 1))))
        self.objs = [
            Obj(top, oh, ow,
                int(rng.integers(lo, hi + 1)) * int(rng.choice((-1, 1))))
            for top, oh, ow in rows]
        self.x0 = [int(rng.integers(0, self.w)) for _ in self.objs]
        self.sprites = [sprite(rng, o.h, o.w) for o in self.objs]
        self.target = histogram(self.sprites[0], self.bins, self.vr)
        if self.fixed:
            p = float(mix.get("scene_change_prob", 0.0))
            changes = rng_for(seed, client, 2).random(_SCENE_HORIZON) < p
            changes[0] = False
            self._scene = np.cumsum(changes)
        else:
            pan = mix["pan"]
            self.canvas_h = self.h + pan["rows"]
            self.canvas_w = self.w + pan["cols"]
            self.pan_speed = int(rng.integers(pan["speed"][0],
                                              pan["speed"][1] + 1))
        self._bg_key = None
        self._bg = None

    # -- frames ------------------------------------------------------------
    def _background(self, key) -> np.ndarray:
        if key != self._bg_key:
            h, w = ((self.h, self.w) if self.fixed
                    else (self.canvas_h, self.canvas_w))
            rng = rng_for(self.seed, self.client, 3, key)
            self._bg = background(rng, h, w)
            if "flat" in self.mix:
                # the rows a crop can start below the canvas's top, and
                # then the share of a frame's rows
                rows = h - self.h + math.ceil(self.mix["flat"]["share"]
                                              * self.h)
                self._bg[:rows] = rng.integers(0, 256)
            self._bg_key = key
        return self._bg

    def scene(self, t: int) -> int:
        return int(self._scene[t % _SCENE_HORIZON]) if self.fixed else 0

    def obj_left(self, i: int, t: int) -> int:
        o = self.objs[i]
        return (self.x0[i] + o.speed * t) % (self.w + o.w) - o.w

    def frame(self, t: int) -> np.ndarray:
        if self.fixed:
            img = self._background(self.scene(t)).copy()
        else:
            clip, k = divmod(t, self.mix["clip_frames"])
            bg = self._background(clip)
            span_c = self.canvas_w - self.w
            span_r = self.canvas_h - self.h
            c = _triangle(k * self.pan_speed, span_c)
            r = _triangle(k, span_r)
            img = bg[r:r + self.h, c:c + self.w].copy()
        for i, (o, spr) in enumerate(zip(self.objs, self.sprites)):
            paste(img, spr, o.top, self.obj_left(i, t))
        return img

    # -- queries -----------------------------------------------------------
    def queries(self, t: int, frame: np.ndarray) -> list:
        from repro.core.engine import (LikelihoodQuery, MultiScaleQuery,
                                       RegionQuery)

        out = []
        rng = rng_for(self.seed, self.client, 4, t)
        for q in self.mix["queries"]:
            kind = q["kind"]
            if kind == "fragments":
                out.append(RegionQuery(self.fragments(q, rng, t)))
                continue
            target = self._target(q, rng, frame)
            if kind == "likelihood":
                out.append(LikelihoodQuery(target, tuple(q["window"]),
                                           stride=q["stride"]))
            elif kind == "multiscale":
                out.append(MultiScaleQuery(
                    target, tuple(tuple(w) for w in q["windows"]),
                    stride=q["stride"]))
            else:
                raise ValueError(f"unknown query kind {kind!r}")
        return out

    def _target(self, q: dict, rng: np.random.Generator,
                frame: np.ndarray) -> np.ndarray:
        if q["target"] == "object":
            return self.target
        ph, pw = q["patch"]
        r = int(rng.integers(0, self.h - ph + 1))
        c = int(rng.integers(0, self.w - pw + 1))
        return histogram(frame[r:r + ph, c:c + pw], self.bins, self.vr)

    def fragments(self, q: dict, rng: np.random.Generator,
                  t: int) -> np.ndarray:
        """(9 * objects, 4) inclusive rects: each object's estimated box,
        jittered, cut into a grid, clipped to the frame."""
        gr, gc = q["grid"]
        j = q["jitter_px"]
        rects = []
        for i, o in enumerate(self.objs):
            top = o.top + int(rng.integers(-j, j + 1))
            left = self.obj_left(i, t) + int(rng.integers(-j, j + 1))
            for a in range(gr):
                for b in range(gc):
                    r0 = top + a * o.h // gr
                    r1 = top + (a + 1) * o.h // gr - 1
                    c0 = left + b * o.w // gc
                    c1 = left + (b + 1) * o.w // gc - 1
                    r0 = min(max(r0, 0), self.h - 1)
                    c0 = min(max(c0, 0), self.w - 1)
                    rects.append((r0, c0, min(max(r1, r0), self.h - 1),
                                  min(max(c1, c0), self.w - 1)))
        return np.asarray(rects, np.int32)


def _triangle(x: int, span: int) -> int:
    """0..span..0 triangle wave of ``x`` (a pan that turns at the edges)."""
    if span <= 0:
        return 0
    x %= 2 * span
    return x if x <= span else 2 * span - x


@functools.lru_cache(maxsize=8)
def _layout_perm(layout_seed: int, run_seed: int, n: int) -> tuple:
    return tuple(int(i) for i in rng_for(layout_seed, run_seed, 5).permutation(n))


def streams(cfg: dict, mix: dict, seed: int) -> list[Stream]:
    """Every client's stream for this seed."""
    n = mix["clients"]
    if mix["camera"] != "fixed":
        return [Stream(cfg, mix, seed, c) for c in range(n)]
    table = layout_table(mix, cfg["height"])
    perm = _layout_perm(mix["layouts"]["seed"], seed, len(table))
    return [Stream(cfg, mix, seed, c, layout=table[perm[c % len(table)]])
            for c in range(n)]


def sampled(seed: int, n: int, every: int) -> bool:
    """Whether the ``n``-th frame of the window (in the order the load
    generator sends them) is among the answers the check compares: one in
    ``every``, from a phase drawn from the seed, so a window of ``N``
    frames always compares ``N // every`` or one more."""
    return every <= 1 or (n + int(rng_for(seed, 6).integers(0, every))) % every == 0
