"""From a profiler trace to the numbers the per-layer metrics read.

``Tracer`` records a window with JAX's profiler (host spans named
``bench.*`` from the benchmark's own code, device operations from the
chip). ``summarize`` reduces the ``.xplane.pb`` file:

  window    from the first ``bench.*`` host span recorded to the end of
            the last one (the part of the benchmark's window the
            profiler saw);
  busy      the union of the device's operation intervals inside the
            window (``XLA Ops`` lines of the ``/device:TPU:*`` planes,
            averaged over the chips); each operation's own time (less
            the operations nested in it, as a ``while`` holds its body)
            is summed by HLO instruction name for the breakdown;
  modules   device time of each execution of a jitted program, by class:
            ``decode`` for the engine's fused decode program, ``prefill``
            for its fused fill program (``XLA Modules`` lines);
  kernel    device time of the blocked CiM Pallas kernel's operations,
            by the class of the program they ran in;
  idle gaps the device's idle intervals, each charged to the innermost
            ``bench.*`` host span in progress at its middle. The device's
            clock runs about a millisecond apart from the host's, so this
            attribution is good to about that.

The names below are what the program gives its programs and its kernel.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re
import shutil
import time

# jitted programs of serve/engine.ContinuousBatcher, by the name of the
# function it jits, and the Pallas kernel of kernels/ternary_mac.py
MODULE_CLASSES = (("decode", re.compile(r"^jit_step(\(|$|\.)")),
                  ("prefill", re.compile(r"^jit_pf(\(|$|\.)")))
CIM_KERNEL = re.compile(r"^(ternary_cim_matmul|_?cim_mac_kernel)$")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPAN = "bench."


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: dict          # class -> [seconds per execution]
    kernel_s: dict         # class -> seconds of CiM kernel
    ops: dict              # operation name -> seconds
    idle: dict             # host span name -> idle device seconds
    chips: int
    programs: dict = dataclasses.field(default_factory=dict)  # name -> count

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.idle)}


def _classify(name: str):
    for cls, pat in MODULE_CLASSES:
        if pat.search(name):
            return cls
    return None


def op_name(name: str) -> str:
    """``%ternary_cim_matmul.20 = f32[...] custom-call(...)`` -> the HLO
    instruction's base name, ``ternary_cim_matmul``."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].strip().lstrip("%"))


def _self_times(events):
    """Durations less the time of events nested inside them on the same
    line (a ``while`` op contains its body's ops)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    out += [tuple(x) for x in stack]
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read_events(path: str):
    """(device lines, host spans) of an ``.xplane.pb``: device lines as
    {plane: {line: [(name, start_ns, end_ns)]}}, host spans as
    [(name, start_ns, end_ns)] of the benchmark's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith(HOST_SPAN)]
    return device, host


def summarize(device: dict, host: list) -> Summary:
    if not device:
        raise ValueError("the trace holds no TPU device plane")
    if host:
        w0, w1 = min(s for _, s, _ in host), max(e for _, _, e in host)
    else:
        ext = [(s, e) for lines in device.values() for s, e in
               ((s, e) for _, s, e in lines.get("XLA Modules", []))]
        w0, w1 = min(s for s, _ in ext), max(e for _, e in ext)
    clip = lambda s, e: (max(s, w0), min(e, w1))
    busy = 0.0
    modules = collections.defaultdict(list)
    kernel = collections.defaultdict(float)
    ops = collections.defaultdict(float)
    idle = collections.defaultdict(float)
    programs = collections.Counter()
    spans = sorted(host, key=lambda h: h[1])
    span_starts = [h[1] for h in spans]
    for lines in device.values():
        mods = sorted((s, e, _classify(n)) for n, s, e in lines.get("XLA Modules", []))
        for n, s, e in lines.get("XLA Modules", []):
            if s >= w0 and e <= w1:
                programs[n.split("(")[0]] += 1
        for s, e, cls in mods:
            if cls and s >= w0 and e <= w1:
                modules[cls].append((e - s) * 1e-9)
        starts = [m[0] for m in mods]
        busy_iv = []
        for name, s, e, own in _self_times(lines.get("XLA Ops", [])):
            cs, ce = clip(s, e)
            if ce <= cs:
                continue
            busy_iv.append((cs, ce))
            ops[op_name(name)] += own * (ce - cs) / (e - s) * 1e-9
            if CIM_KERNEL.search(op_name(name)):
                mid = (cs + ce) / 2
                i = bisect.bisect_right(starts, mid) - 1
                if i >= 0 and mods[i][1] >= mid and mods[i][2]:
                    kernel[mods[i][2]] += (ce - cs) * 1e-9
        merged = _union(busy_iv)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            j = bisect.bisect_right(span_starts, mid)
            inside = [h for h in spans[max(0, j - 4):j] if h[2] >= mid]
            who = min(inside, key=lambda h: h[2] - h[1])[0] if inside else "no span"
            idle[who] += (ge - gs) * 1e-9
    chips = len(device)
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy / chips,
                   modules=dict(modules), kernel_s=dict(kernel), ops=dict(ops),
                   idle={k: v / chips for k, v in idle.items()}, chips=chips,
                   programs=dict(programs))


class Tracer:
    """Profiler on for the end of a window: ``start()`` when the traced
    part begins (returns the host clock), ``stop()`` after the window,
    returning the trace's ``Summary`` and deleting the trace files.
    ``span(name)`` marks host work on the profiler's clock."""

    def __init__(self, log_dir: str):
        self.dir = log_dir

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> float:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return time.perf_counter()

    def stop(self) -> Summary:
        import jax

        jax.profiler.stop_trace()
        try:
            files = sorted(glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True))
            return summarize(*read_events(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

