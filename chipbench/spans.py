"""The reduction of the program's own spans in a profiler trace.

    python3 chipbench/spans.py <trace .xplane.pb, or a profiler log dir>

The service and the engine open a ``jax.profiler.TraceAnnotation`` at
each of their boundaries (``PROGRAM_SPANS``; ``repro/serve/service.py``,
``repro/core/engine.py``).  They land on the ``/host:CPU`` plane, one
line per thread, on the device lines' clock, and carry their attributes
as the event's stats.  This module reads them beside what ``trace.py``
reads, and prints for the traced stretch:

* ``idle_gaps``: the ten longest idle gaps of the devices, each labelled
  by the benchmark span the host was in (``trace.GAP_LABELS``) and, where
  the benchmark had none open, by the most specific program span open
  (``GAP_LABELS`` here);
* ``engine``: per program ``engine.run``, the time in each stage and the
  rest (self time), and the share of ``engine.run`` its stages cover;
* ``threads``: per host line that served, its time waiting, in batches,
  and in ``engine.run``, and the queue wait of its requests.

``Stretch.spans`` is what the program-span metric readers
(``metrics/_program.py``) read.  The benchmark's runs do not fill it yet:
``runner.execute`` keeps ``trace.Summary``, which holds no program span.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import sys
import warnings

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace  # noqa: E402

#: every span the program emits; a name missing here is read by nothing.
PROGRAM_SPANS = ("service.wait", "service.batch", "service.group",
                 "service.resolve", "engine.run", "engine.plan",
                 "engine.validate", "engine.update", "engine.compute",
                 "engine.query")
#: most specific first, for the gaps no benchmark span labels.
GAP_LABELS = ("engine.validate", "engine.plan", "engine.update",
              "engine.compute", "engine.query", "engine.run",
              "service.resolve", "service.group", "service.batch",
              "service.wait")
#: the children of ``engine.run``, in the order it runs them.
ENGINE_STAGES = ("engine.plan", "engine.validate", "engine.update",
                 "engine.compute", "engine.query")


@dataclasses.dataclass
class Span:
    start: float                    # seconds, on the device lines' clock
    end: float
    name: str
    stats: dict
    line: int                       # index of its host line (thread)


@dataclasses.dataclass
class Stretch:
    """The program's side of one traced stretch."""

    window_s: float
    spans: dict                     # name -> [(start_s, dur_s, {stat: v})]
    idle_gaps: list                 # [[label, seconds]], longest first
    engine: dict                    # engine_split() of the stretch
    threads: dict                   # thread_split() of the stretch


def program_spans(path: str) -> list:
    """Every program span of a trace file, with its stats and line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in PROGRAM_SPANS:
                    t0 = e.start_ns * 1e-9
                    with warnings.catch_warnings():   # jaxlib's stats type
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    out.append(Span(t0, t0 + e.duration_ns * 1e-9, e.name,
                                    stats, i))
    return out


def is_program_run(span: Span) -> bool:
    """The program's ``engine.run`` carries its plan; the benchmark's
    wrapper of the same name carries nothing."""
    return span.name == "engine.run" and "representation" in span.stats


def _gaps(devices, w0: float, w1: float) -> list:
    """[(start, end)] of every device's idle time in the window."""
    out = []
    for dev in devices:
        busy = trace.union((max(a, w0), min(b, w1))
                           for a, b, _ in dev["XLA Modules"]
                           if b > w0 and a < w1)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        out += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return out


def _label(mid: float, bench: list, program: list) -> str:
    for s in bench:
        if s[0] <= mid <= s[1]:
            return s[2]
    for s in program:
        if s.start <= mid <= s.end:
            return s.name
    return "no span"


def engine_split(spans: list) -> dict:
    """Per program ``engine.run``: mean ms in each stage and in the rest
    (``self``), and the share of ``engine.run`` time the stages cover;
    overall and per ``representation`` (``+incremental`` when it was)."""
    by_line = collections.defaultdict(list)
    for s in spans:
        if s.name in ENGINE_STAGES:
            by_line[s.line].append(s)
    groups = collections.defaultdict(list)
    for run in filter(is_program_run, spans):
        inside = collections.Counter()
        for s in by_line[run.line]:
            if run.start <= s.start and s.end <= run.end:
                inside[s.name] += s.end - s.start
        kind = run.stats["representation"] + (
            "+incremental" if run.stats.get("incremental") else "")
        for key in ("all", kind):
            groups[key].append((run.end - run.start, inside))
    out = {}
    for key, runs in groups.items():
        total = sum(d for d, _ in runs)
        staged = {n: sum(c[n] for _, c in runs) for n in ENGINE_STAGES}
        out[key] = {
            "runs": len(runs),
            "run_ms": 1e3 * total / len(runs),
            **{n.split(".")[1] + "_ms": 1e3 * v / len(runs)
               for n, v in staged.items() if v},
            "self_ms": 1e3 * (total - sum(staged.values())) / len(runs),
            "covered": sum(staged.values()) / total if total else None,
        }
    return out


def thread_split(spans: list) -> dict:
    """Per host line that served: seconds waiting for work, in batches
    and in ``engine.run``; its batches, requests, and their mean queue
    wait in ms; the replica's device."""
    out = {}
    for line in sorted({s.line for s in spans}):
        mine = [s for s in spans if s.line == line]
        batches = [s for s in mine if s.name == "service.batch"]
        size = sum(s.stats.get("size", 0) for s in batches)
        wait_us = sum(s.stats.get("wait_us", 0.0) for s in batches)
        out[str(line)] = {
            "device": batches[0].stats.get("device") if batches else None,
            "wait_s": sum(s.end - s.start for s in mine
                          if s.name == "service.wait"),
            "batch_s": sum(s.end - s.start for s in batches),
            "engine_s": sum(s.end - s.start for s in mine
                            if is_program_run(s)),
            "batches": len(batches),
            "requests": size,
            "queue_wait_ms": 1e-3 * wait_us / size if size else None,
        }
    return out


def summarize(devices, bench_spans, spans, top: int = 10) -> Stretch | None:
    """The program's side of the stretch inside the ``bench.trace``
    span; ``None`` when the trace has no such span."""
    window = [s for s in bench_spans if s[2] == trace.WINDOW_SPAN]
    if not window:
        return None
    w0, w1 = window[0][0], window[0][1]
    inside = [s for s in spans if w0 <= s.start < w1]
    bench = sorted((s for s in bench_spans if s[2] != trace.WINDOW_SPAN),
                   key=lambda s: trace.GAP_LABELS.index(s[2]))
    labelled = sorted((s for s in spans if s.end > w0 and s.start < w1),
                      key=lambda s: GAP_LABELS.index(s.name))
    gaps = sorted(((b - a, _label((a + b) / 2, bench, labelled))
                   for a, b in _gaps(devices, w0, w1)), reverse=True)
    named = collections.defaultdict(list)
    for s in sorted(inside, key=lambda s: s.start):
        named[s.name].append((s.start, s.end - s.start, s.stats))
    return Stretch(window_s=w1 - w0, spans=dict(named),
                   idle_gaps=[[label, d] for d, label in gaps[:top]],
                   engine=engine_split(inside), threads=thread_split(inside))


def read(path: str) -> Stretch | None:
    """``summarize`` of a trace file, or of the newest one in a
    profiler log directory."""
    if os.path.isdir(path):
        path = trace.latest_xplane(path)
    devices, bench_spans = trace.events(path)
    return summarize(devices, bench_spans, program_spans(path))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/spans.py <trace .xplane.pb | "
              "profiler log dir>", file=sys.stderr)
        return 2
    s = read(argv[0])
    if s is None:
        print("spans: no bench.trace window in the trace", file=sys.stderr)
        return 1
    print(json.dumps({"window_s": s.window_s, "idle_gaps": s.idle_gaps,
                      "engine": s.engine, "threads": s.threads}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
