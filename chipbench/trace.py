"""The reduction from a profiler trace to device metrics.

A traced run records one stretch of its window with ``jax.profiler``
(Python tracer off) inside a ``bench.trace`` annotation.  The trace's
``/device:TPU:<i>`` planes carry two lines this module reads:

* ``XLA Modules``: one event per program the device ran
  (``jit__integral_histogram_jit(<hash>)``, ``jit_gather(<hash>)``, ...).
  Their union is the device's busy time.
* ``XLA Ops``: one event per HLO op, named by its HLO text
  (``%name = f32[1,32,1152,1920]{...} custom-call(...)``).  A Pallas
  kernel is the ``custom-call`` op inside the program that wraps it (the
  table ``KERNEL_PROGRAMS`` names those programs, for traces whose
  kernels carry no ``name=`` of their own), or a ``custom-call`` op
  named after the kernel (``%wf_tis.1 = ...``) in any program, as in the
  program that shards H's bins over chips.

Host spans (``TraceAnnotation``) are on the ``/host:CPU`` plane, on the
same clock, so every idle gap of a device is labelled by the benchmark
span the host was in at the gap's middle.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

#: kernel -> the jitted program whose custom-call op is that kernel.
KERNEL_PROGRAMS = {
    "wf_tis": "jit__integral_histogram_jit",
    "fused_rows": "jit__fused_rows_jit",
    "delta_apply": "jit__delta_apply_jit",
}
WINDOW_SPAN = "bench.trace"
#: most specific first: the label of a gap is the first of these open.
GAP_LABELS = ("validate", "frame.resolve", "query.apply", "engine.run",
              "client.wait")

_SHAPE = re.compile(r"^\S+ = [a-z0-9]+\[([0-9,]*)\]")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r"^(?:ROOT )?%([A-Za-z_][A-Za-z0-9_]*?)(?:\.[0-9]+)? = ")


@dataclasses.dataclass
class Summary:
    """What the readers use from one traced stretch."""

    window_s: float
    busy_s: float                 # mean over the devices traced
    devices: int
    device_ops: list              # [[label, seconds]], most time first
    idle_gaps: list               # [[label, seconds]], longest first
    kernels: dict                 # kernel -> [(seconds, output dims)]


def program_name(event_name: str) -> str:
    """``jit_gather(1234)`` -> ``jit_gather``."""
    return event_name.split("(", 1)[0]


def opcode(op_name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``custom-call``, ...)."""
    m = _OPCODE.search(op_name, op_name.find(" = ") + 1)
    return m.group(1) if m else op_name.split(" ", 1)[0]


def kernel_of(program: str, op_name: str) -> str | None:
    """The kernel a ``custom-call`` op is: by the program around it, or
    by the op's own name; ``None`` for another op."""
    for kernel, p in KERNEL_PROGRAMS.items():
        if program == p:
            return kernel
    m = _OP_NAME.match(op_name)
    return m.group(1) if m and m.group(1) in KERNEL_PROGRAMS else None


def out_dims(op_name: str) -> tuple:
    m = _SHAPE.match(op_name)
    if not m or not m.group(1):
        return ()
    return tuple(int(d) for d in m.group(1).split(","))


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return paths[-1]


def events(path: str):
    """(device lines, host spans) of a trace file: per device plane the
    ``XLA Modules`` and ``XLA Ops`` events as (start_s, end_s, name), and
    the host's benchmark spans as (start_s, end_s, name)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Modules": [], "XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         e.name) for e in line.events]
            devices.append(lines)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in GAP_LABELS:
                        spans.append((e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      e.name))
    return devices, spans


def summarize(devices, spans, top: int = 10) -> Summary | None:
    """Reduce one traced stretch; ``None`` when no device op ran in it."""
    window = [s for s in spans if s[2] == WINDOW_SPAN]
    if not window or not devices:
        return None
    w0, w1 = window[0][0], window[0][1]
    labelled = sorted((s for s in spans if s[2] != WINDOW_SPAN),
                      key=lambda s: GAP_LABELS.index(s[2]))
    busy_total = 0.0
    ops = collections.Counter()
    gaps = []
    kernels = collections.defaultdict(list)
    for dev in devices:
        mods = [(max(a, w0), min(b, w1), n) for a, b, n in dev["XLA Modules"]
                if b > w0 and a < w1]
        busy = union((a, b) for a, b, _ in mods)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                label = next((s[2] for s in labelled if s[0] <= mid <= s[1]),
                             "no span")
                gaps.append((b - a, label))
        starts = sorted(mods)
        k = 0
        for a, b, name in sorted(dev["XLA Ops"]):
            if b <= w0 or a >= w1:
                continue
            while k + 1 < len(starts) and starts[k + 1][0] <= a:
                k += 1
            prog = (program_name(starts[k][2])
                    if starts and starts[k][0] <= a <= starts[k][1] else "?")
            code = opcode(name)
            ops[f"{prog}:{code}"] += min(b, w1) - max(a, w0)
            kernel = kernel_of(prog, name) if code == "custom-call" else None
            if kernel is not None:
                kernels[kernel].append((b - a, out_dims(name)))
    n = len(devices)
    gaps.sort(reverse=True)
    return Summary(
        window_s=w1 - w0, busy_s=busy_total / n, devices=n,
        device_ops=[[k, v] for k, v in ops.most_common(top)],
        idle_gaps=[[label, s] for s, label in gaps[:top]],
        kernels=dict(kernels))


def read(log_dir: str) -> Summary | None:
    return summarize(*events(latest_xplane(log_dir)))
