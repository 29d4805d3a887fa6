"""The comparison that decides ``correct``.

Every answer the timed window produced for a sampled frame is compared
with the plain reference (``reference.py``) computed from the same frame
and query, at the corners of H the frame's queries read
(``reference.corners``).  The numbers compared, each held to the configuration's
limit (``limits`` in ``configs/<config>.json``):

* ``count_mismatch``: region-histogram counts that differ from the exact
  four-corner counts.  Counts are exact by the configuration, so the
  limit is 0.
* ``lik_gap``: the widest gap between a served likelihood value and the
  float64 intersection of the exact window histograms.
* ``ms_gap``: the same for multi-scale maps and best score, and the gap
  between the best score and the reference score of the window the
  program named as best.
* ``lost``: frames due in the window whose answers never came or raised.
* ``chain_splits``: cameras whose frames were served by more than one
  replica (chains are pinned to one replica by the configuration).
* ``checked``: how many frames were compared; a lower bound.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

from chipbench import reference as ref


@dataclasses.dataclass
class Sample:
    """One served frame kept for the check: its input and its answers."""

    client: int
    t: int
    frame: np.ndarray
    queries: list
    answers: list


def _gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    d = np.abs(got - want)
    # a NaN where the reference has a number is a wrong answer
    d = np.where(np.isnan(got) & np.isnan(want), 0.0,
                 np.where(np.isnan(d), np.inf, d))
    return float(d.max()) if d.size else 0.0


#: pixels up to which a float32 H counts every bin exactly (2**24).
FLOAT32_EXACT = 1 << 24


def control_dtype(cfg: dict):
    """The precision of the configuration's control, the one below its
    counts': where a frame has at most 2**24 pixels, float32 counts it
    exactly and the control is bfloat16; past that, counts are exact only
    in an integer H, and the control is float32."""
    if cfg["height"] * cfg["width"] > FLOAT32_EXACT:
        return np.float32
    return ml_dtypes.bfloat16


def grid(frame: np.ndarray, queries, cfg: dict, dtype) -> ref.Corners:
    """The reference's P at every corner ``queries`` read, in ``dtype``."""
    h, w = frame.shape
    rows, cols = [], []
    for q in queries:
        kind = type(q).__name__
        if kind == "RegionQuery":
            r = np.asarray(q.rects)
            rows += [r[:, 0], r[:, 2] + 1]
            cols += [r[:, 1], r[:, 3] + 1]
        elif kind in ("LikelihoodQuery", "MultiScaleQuery"):
            wins = [q.window] if kind == "LikelihoodQuery" else q.windows
            for wh, ww in wins:
                rows.append(ref.window_lattice(h, wh, q.stride))
                cols.append(ref.window_lattice(w, ww, q.stride))
        else:
            raise TypeError(f"no reference for {kind}")
    return ref.corners(frame, cfg["bins"], cfg["value_range"],
                       np.concatenate(rows), np.concatenate(cols), dtype)


def compare(samples, cfg: dict, dtype=np.int32) -> dict:
    """Readings of every number compared over ``samples``; ``dtype`` is
    the precision the reference holds H in (the control lowers it)."""
    mismatch, lik, ms = 0, 0.0, 0.0
    for s in samples:
        P = grid(s.frame, s.queries, cfg, dtype)
        for q, got in zip(s.queries, s.answers):
            kind = type(q).__name__
            if kind == "RegionQuery":
                want = ref.regions(P, q.rects)
                got = np.asarray(got)
                mismatch += (int(np.sum(got != want)) if got.shape == want.shape
                             else int(want.size))
            elif kind == "LikelihoodQuery":
                want = ref.likelihood(P, q.target, q.window, q.stride)
                lik = max(lik, _gap(got, want))
            else:
                maps, best = ref.multiscale(P, q.target, q.windows, q.stride)
                rect, score, got_maps = got
                gaps = [_gap(g, w) for g, w in zip(got_maps, maps)]
                gaps.append(abs(float(score) - best))
                gaps.append(best - ref.score_at(maps, q.windows, q.stride, rect))
                if len(got_maps) != len(maps):
                    gaps.append(float("inf"))
                ms = max(ms, *gaps)
    return {"count_mismatch": mismatch, "lik_gap": lik, "ms_gap": ms,
            "checked": len(samples)}


def reference_answers(sample: Sample, cfg: dict, dtype) -> list:
    """The answers the reference gives with H held in ``dtype``, in the
    program's form (the control puts these in the program's place)."""
    P = grid(sample.frame, sample.queries, cfg, dtype)
    out = []
    for q in sample.queries:
        kind = type(q).__name__
        if kind == "RegionQuery":
            out.append(ref.regions(P, q.rects).astype(np.float32))
        elif kind == "LikelihoodQuery":
            out.append(ref.likelihood(P, q.target, q.window, q.stride)
                       .astype(np.float32))
        else:
            maps, _ = ref.multiscale(P, q.target, q.windows, q.stride)
            k, flat = max(((i, int(np.argmax(m))) for i, m in enumerate(maps)),
                          key=lambda kf: maps[kf[0]].flat[kf[1]])
            (wh, ww), m = q.windows[k], maps[k]
            r0 = flat // m.shape[1] * q.stride
            c0 = flat % m.shape[1] * q.stride
            out.append((np.array([r0, c0, r0 + wh - 1, c0 + ww - 1]),
                        np.float32(m.flat[flat]),
                        [x.astype(np.float32) for x in maps]))
    return out


def control(samples, cfg: dict, dtype) -> dict:
    """Readings of the control: the reference with H held in ``dtype``
    put in the program's place, for the same frames and queries."""
    return compare([Sample(s.client, s.t, s.frame, s.queries,
                           reference_answers(s, cfg, dtype))
                    for s in samples], cfg)


def chain_splits(touched: dict) -> int:
    """Cameras whose frames more than one replica touched."""
    return sum(1 for replicas in touched.values() if len(replicas) > 1)


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every reading within its
    limit; ``checked`` is a lower bound, every other limit an upper one."""
    out, ok = {}, True
    for name, value in readings.items():
        limit = limits[name]
        good = value >= limit if name == "checked" else value <= limit
        ok = ok and bool(good)
        out[name] = {"value": value, "limit": limit}
    return ok, out
