"""Profiler traces: capture, and the reduction from trace to numbers.

A traced run records one ``.xplane.pb`` with the JAX profiler (Python
tracer off, so the file stays small) and reduces it here:

* **busy** — the union of the intervals in which an operation ran on a
  device, inside a window; the idle share is one minus busy over window;
* **exposed collectives** — the time in which a collective ran on a device
  while no other operation ran there;
* **named calls** — the device time of the compiled modules whose name
  holds a given string (the benchmark jits each measured kernel call
  under a name of its own);
* **breakdown** — the device operations that took most time, and the
  longest idle gaps, each named by the host span it fell in.

The benchmark's own host spans are ``jax.profiler.TraceAnnotation``\\ s
named ``bench.*``; they set the windows.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all", "collective-broadcast")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    start: int          # ns
    end: int            # ns
    name: str

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict           # device id -> [Event] on the operations line
    modules: dict       # device id -> [Event] on the modules line
    spans: list         # [Event] bench.* host spans
    host: list          # [Event] every other host event (for naming gaps)
    async_ops: dict = dataclasses.field(default_factory=dict)
                        # device id -> [Event] start-to-done of async ops


@contextlib.contextmanager
def capture(logdir: str):
    """Profile the enclosed block into ``logdir`` (emptied first)."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    with jax.profiler.trace(logdir, profiler_options=opts):
        yield


def xplane_path(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def op_name(name: str) -> str:
    """A device operation's event name is its HLO instruction text,
    ``%fusion.93 = f32[134217728]{0:T(1024)} fusion(...), ...``: keep the
    instruction's name, result type and opcode."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    shape = re.search(r"[a-z]+\d*\[[^\]]*\]", rhs)
    opcode = re.search(r" ([a-z][\w\-]*)\(", rhs)
    parts = [lhs.lstrip("%"), shape.group(0) if shape else "",
             opcode.group(1) if opcode else ""]
    return " ".join(p for p in parts if p)


def _events(line, name=lambda n: n) -> list:
    return [Event(int(e.start_ns), int(e.start_ns + e.duration_ns),
                  name(e.name)) for e in line.events]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into device operations, device modules and
    host events, all on the profiler's one clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, async_ops, spans, host = {}, {}, {}, [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(_events(line, op_name))
                elif line.name == ASYNC_LINE:
                    async_ops.setdefault(dev, []).extend(
                        _events(line, op_name))
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    (spans if ev.name.startswith(SPAN_PREFIX)
                     else host).append(ev)
    for d in (ops, modules, async_ops):
        for evs in d.values():
            evs.sort(key=lambda e: (e.start, -e.end))
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans, host, async_ops)


def span(trace: Trace, name: str) -> Event:
    """The first host span called ``name``."""
    for s in trace.spans:
        if s.name == name:
            return s
    raise KeyError(f"no host span {name!r} in the trace "
                   f"(have {sorted({s.name for s in trace.spans})})")


def _clip(events, lo: int, hi: int) -> list:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def covered_ns(events, lo: int, hi: int) -> int:
    """ns of ``[lo, hi)`` that at least one of ``events`` covers."""
    return _length(_union(_clip(events, lo, hi)))


def leaves(events: list) -> list:
    """Events that contain no other event of the same line (a loop or call
    op spans the operations it runs)."""
    out, stack = [], []
    for e in events:                       # sorted by (start, -end)
        while stack and stack[-1][0].end <= e.start:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out.extend(top for top, has_child in stack if not has_child)
    return out


def busy_ns(trace: Trace, lo: int, hi: int) -> dict:
    """Device id -> ns inside ``[lo, hi)`` in which some operation ran."""
    return {d: covered_ns(evs, lo, hi) for d, evs in trace.ops.items()}


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def exposed_collective_ns(trace: Trace, lo: int, hi: int,
                          counted=None) -> dict:
    """Device id -> ns inside ``[lo, hi)`` in which a collective ran (as an
    operation, or between an async collective's start and done) while no
    other (leaf) operation ran on that device.  ``counted(event)``, when
    given, narrows the collectives counted; the others count as other
    operations."""
    def coll_(e):
        return is_collective(e.name) and (counted is None or counted(e))

    out = {}
    for d, evs in trace.ops.items():
        lv = leaves(evs)
        pending = [e for e in trace.async_ops.get(d, []) if coll_(e)]
        coll = _union(_clip([e for e in lv if coll_(e)] + pending, lo, hi))
        comp = _union(_clip([e for e in lv if not coll_(e)], lo, hi))
        out[d] = _length(coll) - _overlap(coll, comp)
    return out


def _overlap(xs, ys) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def module_ns(trace: Trace, name: str) -> dict:
    """Device id -> summed device time of the modules whose name holds
    ``name``."""
    out = {}
    for d, evs in trace.modules.items():
        sel = [e for e in evs if name in e.name]
        if sel:
            out[d] = sum(e.dur for e in sel)
    return out


def _host_name(trace: Trace, t: int) -> str:
    """``bench-span/innermost-host-event`` at instant ``t``."""
    outer = [s for s in trace.spans if s.start <= t < s.end]
    name = min(outer, key=lambda s: s.dur).name if outer else "outside"
    inner = [e for e in trace.host if e.start <= t < e.end]
    if inner:
        name += "/" + min(inner, key=lambda e: e.dur).name[:80]
    return name


def breakdown(trace: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """The ``top`` device operations by self time summed over devices, and
    the ``top`` longest idle gaps (on any device), in seconds."""
    self_ns: dict = {}
    gaps = []
    for d, evs in trace.ops.items():
        for e in leaves(evs):
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                self_ns[e.name] = self_ns.get(e.name, 0) + (b - a)
        busy = _union(_clip(evs, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a))
    ops = sorted(self_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, reverse=True)[:top]
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[_host_name(trace, a + g // 2), g / 1e9]
                      for g, a in gaps],
    }
