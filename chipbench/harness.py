"""Drive one cell: build the service, warm it up, run the timed window,
and record what the metrics read.

The window drives ``AnalyticsService.submit`` (``DistributedAnalyticsService
.submit`` for a cell whose mix asks for replicas) in one of two loops:

* open (``"loop": "open"``): frames are due on a fixed clock (staggered
  cameras, a small jitter) whatever the service does; a frame's latency
  runs from when it was due to when its last answer is on the host.
* closed (``"loop": "closed"``): each client keeps one frame in flight
  and sends the next when every answer of the last is on the host.

One thread drives all clients (and, in the open loop, one more brings
finished answers to the host), so the load comes from one process with
few threads whatever the number of clients.

Everything the benchmark measures about the program comes from its own
wrappers: a resolver around the frame store, and the instance attributes
``run`` and ``validate`` of each engine, which the service calls.  Spans
(``jax.profiler.TraceAnnotation``) are recorded only in a traced run.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import queue
import threading
import time

import numpy as np

from chipbench import scene
from chipbench.check import Sample

_WAIT_PAST_CLOSE_S = 60.0


@dataclasses.dataclass
class FrameRec:
    client: int
    t: int
    due: float
    n: int = 0                      # its place among the window's frames
    submit: float | None = None
    done: float | None = None
    rejected: bool = False
    error: str | None = None


class Recorder:
    """Plan mix, host spans, compile count and, per camera, the service
    threads that resolved its frames (one per replica)."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.plans = collections.Counter()
        self.span_s = collections.defaultdict(list)   # name -> (t0, s)
        self.touched = collections.defaultdict(set)   # camera -> threads
        self.compiles = 0
        self._lock = threading.Lock()
        self._classes = {}                  # query class -> traced subclass

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.traced:
            yield
            return
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.span_s[name].append((t0, dt))

    def on_compile(self, event: str, duration_s: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1

    def instrument(self, engine):
        """Wrap ``engine.run`` and ``engine.validate`` on the instance."""
        run, validate = engine.run, engine.validate

        def traced_run(frames, queries=(), *, prev=None):
            with self.span("engine.run"):
                out = run(frames, queries, prev=prev)
            p = out.plan
            kind = p.representation + ("+incremental" if p.incremental else "")
            with self._lock:
                self.plans[kind] += 1
            return out

        def traced_validate(*args, **kwargs):
            with self.span("validate"):
                return validate(*args, **kwargs)

        engine.run = traced_run
        engine.validate = traced_validate
        return engine

    def wrap_queries(self, queries) -> list:
        """Each query with its ``apply`` inside a ``query.apply`` span."""
        if not self.traced:
            return queries
        return [self._traced_class(type(q))(
            **{f.name: getattr(q, f.name) for f in dataclasses.fields(q)})
            for q in queries]

    def _traced_class(self, cls):
        if cls not in self._classes:
            span = self.span

            def apply(q, source):
                with span("query.apply"):
                    return cls.apply(q, source)

            self._classes[cls] = type(cls.__name__, (cls,), {"apply": apply})
        return self._classes[cls]


class FrameStore:
    """The frames the service resolves, kept from before they are due
    until their successors no longer need them."""

    def __init__(self, rec: Recorder, keep: int):
        self._frames = {}
        self._lock = threading.Lock()
        self._rec = rec
        self._keep = keep

    def put(self, ref, frame) -> None:
        client, t = ref
        with self._lock:
            self._frames[ref] = frame
            self._frames.pop((client, t - self._keep), None)

    def drop(self, ref) -> None:
        with self._lock:
            self._frames.pop(ref, None)

    def __call__(self, ref):
        with self._rec.span("frame.resolve"):
            self._rec.touched[ref[0]].add(threading.get_ident())
            with self._lock:
                return self._frames[ref]


def _host(answer):
    """The answer with every array on the host (waits for the device)."""
    import jax

    return jax.tree_util.tree_map(np.asarray, answer)


def build_service(cfg: dict, mix: dict, store: FrameStore, rec: Recorder,
                  devices):
    """The service a cell drives, every engine instrumented.

    The cell's devices are laid out as a ``(replicas, shards)`` mesh on
    the axes ``("data", "model")``, with the mix's ``replicas`` (1 where
    not given) and ``shards`` the devices left to each: frames are routed
    over replicas, and each replica's engine shards H's bins over its
    ``shards`` devices.  With no devices (the CPU tests) every replica is
    one engine on the default device, unsharded."""
    from repro.core.engine import HistogramEngine
    from repro.serve import (AnalyticsService, DistributedAnalyticsService,
                             sharded_engine_factory)

    chained = mix["chain"]

    def predecessor(ref):
        return (ref[0], ref[1] - 1) if chained and ref[1] > 0 else None

    svc_kw = dict(cfg["service"])
    replicas = mix.get("replicas", 1)
    queries = len(mix["queries"])
    if mix["loop"] == "open":
        pending = mix["rate_fps"] * queries * mix["max_pending_seconds"]
    else:
        pending = mix["clients"] * queries
    svc_kw["max_pending"] = max(64, int(math.ceil(pending)))
    shards = 1 if devices is None else len(devices) // replicas
    if replicas == 1 and shards == 1:
        engine = rec.instrument(HistogramEngine(
            cfg["bins"], value_range=cfg["value_range"], **cfg["engine"]))
        return AnalyticsService(engine, store, predecessor=predecessor,
                                **svc_kw)
    engine_kw = dict(cfg["engine"])
    if shards > 1:
        engine_kw["sharding"] = "bin"
    factory = sharded_engine_factory(cfg["bins"],
                                     value_range=cfg["value_range"],
                                     **engine_kw)
    kw = dict(svc_kw, cache_bytes=svc_kw["cache_bytes"] * replicas)
    if devices is not None:
        from repro.compat import make_mesh

        mesh = make_mesh((replicas, shards), ("data", "model"),
                         devices=devices[:replicas * shards])
        kw.update(mesh=mesh, replica_axis="data")
    else:                       # one device: the degenerate layout
        kw.update(num_replicas=replicas)
    return DistributedAnalyticsService(
        lambda sub: rec.instrument(factory(sub)), store,
        predecessor=predecessor, **kw)


def counters(svc) -> dict:
    """Service counters, and per replica for a distributed service."""
    if hasattr(svc, "replicas"):
        snap = svc.snapshot()
        per = [{k: r[k] for k in ("engine_runs", "updated", "requests")}
               for r in snap["replicas"]]
    else:
        snap = svc.stats.snapshot()
        per = []
    keys = ("requests", "engine_runs", "updated", "recomputed", "cache_hits",
            "coalesced", "rejected")
    return {"total": {k: snap[k] for k in keys}, "replicas": per}


@dataclasses.dataclass
class Run:
    """Everything a run records; the per-layer readers read it."""

    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    t0: float = 0.0
    frames: list = dataclasses.field(default_factory=list)
    samples: list = dataclasses.field(default_factory=list)
    start: dict = dataclasses.field(default_factory=dict)
    end: dict = dataclasses.field(default_factory=dict)
    rec: Recorder | None = None
    trace: object = None            # trace.Summary of the traced stretch
    compiles_in_window: int = 0
    device_kind: str = ""

    def done_in_window(self) -> list:
        hi = self.t0 + self.seconds
        return [f for f in self.frames if f.done is not None
                and self.t0 <= f.done <= hi]


class LoadGenerator:
    """Warm-up, then the timed window, for one cell and one seed."""

    def __init__(self, run: Run, svc, store: FrameStore, streams, seed: int):
        self.run, self.svc, self.store = run, svc, store
        self.streams, self.seed = streams, seed
        self.mix = run.mix
        self.every = run.mix["check_every"]

    # -- one frame ---------------------------------------------------------
    def _prepare(self, client: int, t: int):
        s = self.streams[client]
        frame = s.frame(t)
        return frame, self.run.rec.wrap_queries(s.queries(t, frame))

    def _submit(self, rec: FrameRec, frame, queries, done: queue.Queue,
                block: bool) -> bool:
        """Submit a frame's queries; ``done`` receives the job once every
        answer is set.  False when the service refused the frame."""
        from repro.serve import ServiceOverloaded

        ref = (rec.client, rec.t)
        self.store.put(ref, frame)
        rec.submit = time.perf_counter()
        try:
            futures = [self.svc.submit(ref, q, block=block) for q in queries]
        except ServiceOverloaded:
            rec.rejected = True
            return False
        job = (rec, futures, frame, queries)
        left = [len(futures)]
        lock = threading.Lock()

        def on_done(_f):
            with lock:
                left[0] -= 1
                last = left[0] == 0
            if last:
                done.put(job)

        for f in futures:
            f.add_done_callback(on_done)
        return True

    def _finish(self, job, keep: bool) -> None:
        """Bring every answer of a finished frame to the host, stamp it,
        and keep it for the check if the seed samples it."""
        rec, futures, frame, queries = job
        try:
            with self.run.rec.span("client.wait"):
                answers = [_host(f.result(timeout=0)) for f in futures]
            rec.done = time.perf_counter()
        except Exception as e:      # a failed answer is recorded
            rec.error = repr(e)
            return
        if keep and scene.sampled(self.seed, rec.n, self.every):
            self.run.samples.append(
                Sample(rec.client, rec.t, frame, queries, answers))
        if not self.mix["chain"]:
            self.store.drop((rec.client, rec.t))

    # -- closed loop -------------------------------------------------------
    def _closed_loop(self, next_t: list, stop: float, record: bool,
                     frames_each: float = math.inf) -> list:
        """Every client keeps one frame in flight until ``stop`` or until
        it has sent ``frames_each``; one thread drives them all.  Returns
        each client's next frame index."""
        done: queue.Queue = queue.Queue()
        t_next = list(next_t)
        sent = [0] * len(self.streams)
        ready = {c: self._prepare(c, t_next[c]) for c in range(len(sent))}
        inflight = 0

        def send(c):
            nonlocal inflight
            frame, queries = ready.pop(c)
            rec = FrameRec(c, t_next[c], due=time.perf_counter(),
                           n=len(self.run.frames))
            if record:
                self.run.frames.append(rec)
            self._submit(rec, frame, queries, done, block=True)
            inflight += 1
            sent[c] += 1
            t_next[c] += 1
            ready[c] = self._prepare(c, t_next[c])

        for c in range(len(sent)):
            send(c)
        deadline = stop + _WAIT_PAST_CLOSE_S
        while inflight:
            try:
                job = done.get(timeout=_left(deadline))
            except queue.Empty:
                for rec in self.run.frames:
                    if rec.done is None and rec.error is None:
                        rec.error = "no answer by the deadline"
                break
            inflight -= 1
            self._finish(job, keep=record)
            rec = job[0]
            if rec.error is not None and not record:
                raise RuntimeError(f"warm-up frame {(rec.client, rec.t)} "
                                   f"failed: {rec.error}")
            if time.perf_counter() < stop and sent[rec.client] < frames_each:
                send(rec.client)
        return t_next

    def warm_up(self) -> list:
        """Each client's first frames, all clients at once: every shape
        the cell's traffic uses compiles here.  Returns each client's
        next frame index."""
        return self._closed_loop([0] * len(self.streams), math.inf,
                                 record=False,
                                 frames_each=self.mix["warmup_frames"])

    def window(self, next_t: list, on_start) -> None:
        run = self.run
        run.t0 = time.perf_counter() + 0.05
        stop = run.t0 + run.seconds
        on_start(run.t0)
        time.sleep(max(0.0, run.t0 - time.perf_counter()))
        if self.mix["loop"] == "closed":
            self._closed_loop(next_t, stop, record=True)
        else:
            self._open_loop(next_t, stop)

    # -- open loop ---------------------------------------------------------
    def _schedule(self, next_t: list, stop: float) -> list:
        mix, run = self.mix, self.run
        n = len(self.streams)
        period = n / mix["rate_fps"]
        jitter = mix["jitter_ms"] / 1e3
        out = []
        for c in range(n):
            k = 0
            while True:
                t = next_t[c] + k
                rng = scene.rng_for(self.seed, c, 7, t)
                due = (run.t0 + (c + 0.5) * period / n + k * period
                       + float(rng.uniform(-jitter, jitter)))
                if due >= stop:
                    break
                out.append((due, c, t))
                k += 1
        out.sort()
        return out

    def _open_loop(self, next_t: list, stop: float) -> None:
        """Frames go out when due, whatever the service does; a second
        thread brings the answers of each finished frame to the host."""
        done: queue.Queue = queue.Queue()
        deadline = stop + _WAIT_PAST_CLOSE_S

        def finisher():
            while (job := done.get()) is not None:
                self._finish(job, keep=True)

        th = threading.Thread(target=finisher, name="bench-finish",
                              daemon=True)
        th.start()
        for due, c, t in self._schedule(next_t, stop):
            frame, queries = self._prepare(c, t)
            rec = FrameRec(c, t, due=due, n=len(self.run.frames))
            self.run.frames.append(rec)
            time.sleep(max(0.0, due - time.perf_counter()))
            self._submit(rec, frame, queries, done, block=False)
        while any(f.done is None and f.error is None and not f.rejected
                  for f in self.run.frames) and time.perf_counter() < deadline:
            time.sleep(0.01)
        done.put(None)
        th.join(timeout=5.0)
        for f in self.run.frames:
            if f.done is None and f.error is None and not f.rejected:
                f.error = "no answer by the deadline"


def _left(deadline: float) -> float | None:
    """Seconds to ``deadline`` for a wait; ``None`` for no deadline."""
    if math.isinf(deadline):
        return None
    return max(0.0, deadline - time.perf_counter())


def percentile(values, q: float) -> float:
    """The ``q`` quantile of ``values`` by rank (no interpolation), so a
    miss counted as infinite stays in the tail."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    return float(v[max(0, math.ceil(q * v.size) - 1)])
