"""Device time by the program's own scopes, and the program's host spans.

The program names its layers (``src/repro/utils/trace.py``): device scopes
``repro.*`` in the HLO ``op_name`` metadata of what it compiles, and host
spans ``repro.*``.  In a profile a device operation is named only by its
HLO instruction, which is local to its module (``fusion.103`` of
``jit_solve_chunk``).  The device plane of the ``.xplane.pb`` keeps one
event-metadata entry per (module, instruction) that ran, and its stats
hold the module's ``program_id`` and the instruction's ``op_name`` (the
``tf_op`` stat; a fusion carries its root instruction's).  So each
operation maps to its scope through its own metadata entry, with no
lookup by name.  ``jax.profiler.ProfileData`` does not expose those stats,
nor a host event's stats, so this module reads the file with the XPlane
schema built below.

* **scope** of an operation: the innermost ``repro.*`` component of its
  ``op_name``; None for an operation outside every scope (XLA's own
  copies, the loop's bookkeeping);
* **scope time**: device self time of the leaf operations in a scope,
  inside a window.  An operation of no duration does not make the one it
  starts in a container: the chip records zero-length custom calls at the
  start of a fusion;
* **calls**: executions of the scope's operation that reads the table,
  its costliest per execution, and of any other of its operations within
  a factor two of that (the same gather compiled at another call site).
  A kernel's own operations need not run back to back: the profile puts
  the compiler's relayout loops, which carry no ``op_name`` of their own,
  under the enclosing loop's scope, between a gather and its row sum;
* **program spans**: the host spans named ``repro.*`` with their stats
  (``repro.solve`` carries ``host_transfers``, the program's counter over
  the solve);
* **sync idle**: the time in which every device is idle while the host
  is inside given program spans.

The events and their clock are those of ``bench.trace.load``.
"""

from __future__ import annotations

import dataclasses
import functools

from bench import trace as tr

PROGRAM_PREFIX = "repro."
SPMV = "repro.spmv"
BACKUP = "repro.backup"
EXCHANGE = "repro.exchange"
SOLVE_SPAN = "repro.solve"
HOST_WAITS = ("repro.driver.sync", "repro.driver.readback")
TRANSFERS_STAT = "host_transfers"


@dataclasses.dataclass
class Op(tr.Event):
    scope: str | None = None


@dataclasses.dataclass
class Profile:
    trace: tr.Trace     # device operations are Op, with their scope
    program: list       # [(Event, stats)] host spans named repro.*


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The XSpace message class, from the XPlane schema (tsl's
    ``xplane.proto``, field numbers as published), without TensorFlow."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, DBL, STR, BYT, MSG = (F.TYPE_INT64, F.TYPE_UINT64,
                                    F.TYPE_DOUBLE, F.TYPE_STRING,
                                    F.TYPE_BYTES, F.TYPE_MESSAGE)
    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields, oneof=None):
        m = fd.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, label, *ref in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if ftype == MSG:
                f.type_name = ".bench_xplane." + ref[0]
            if oneof and number >= 2:
                f.oneof_index = 0

    message("XStat", [("metadata_id", 1, I64, one),
                      ("double_value", 2, DBL, one),
                      ("uint64_value", 3, U64, one),
                      ("int64_value", 4, I64, one),
                      ("str_value", 5, STR, one),
                      ("bytes_value", 6, BYT, one),
                      ("ref_value", 7, U64, one)], oneof="value")
    message("XEvent", [("metadata_id", 1, I64, one),
                       ("offset_ps", 2, I64, one),
                       ("duration_ps", 3, I64, one),
                       ("stats", 4, MSG, rep, "XStat")])
    message("XLine", [("id", 1, I64, one), ("name", 2, STR, one),
                      ("timestamp_ns", 3, I64, one),
                      ("events", 4, MSG, rep, "XEvent")])
    message("XEventMetadata", [("id", 1, I64, one), ("name", 2, STR, one),
                               ("display_name", 4, STR, one),
                               ("stats", 5, MSG, rep, "XStat")])
    message("XStatMetadata", [("id", 1, I64, one), ("name", 2, STR, one)])
    # map<int64, X> is on the wire a repeated {key = 1, value = 2}
    message("EventMetadataEntry", [("key", 1, I64, one),
                                   ("value", 2, MSG, one, "XEventMetadata")])
    message("StatMetadataEntry", [("key", 1, I64, one),
                                  ("value", 2, MSG, one, "XStatMetadata")])
    message("XPlane", [("id", 1, I64, one), ("name", 2, STR, one),
                       ("lines", 3, MSG, rep, "XLine"),
                       ("event_metadata", 4, MSG, rep,
                        "EventMetadataEntry"),
                       ("stat_metadata", 5, MSG, rep, "StatMetadataEntry")])
    message("XSpace", [("planes", 1, MSG, rep, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stats(stats, stat_names: dict) -> dict:
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        if kind is None:
            continue
        value = getattr(s, kind)
        if kind == "ref_value":           # a string kept once per plane
            value = stat_names.get(value, "")
        out[stat_names.get(s.metadata_id, "")] = value
    return out


def scope_of(op_name: str) -> str | None:
    """The innermost ``repro.*`` component of an ``op_name`` (the profile
    appends ``:<op type>`` to it)."""
    parts = [p for p in op_name.rsplit(":", 1)[0].split("/")
             if p.startswith(PROGRAM_PREFIX)]
    return parts[-1] if parts else None


def _timed(line):
    """``(start, end, event)`` of a line's events, on the clock of
    ``bench.trace.load`` (whole ns)."""
    for e in line.events:
        start = line.timestamp_ns + e.offset_ps // 1000
        yield start, start + e.duration_ps // 1000, e


def load(path: str) -> Profile:
    """Read one ``.xplane.pb``: the events ``bench.trace.load`` reads, each
    device operation with its scope, and the program's host spans with
    their stats."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, async_ops, spans, host, program = {}, {}, {}, [], [], []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        names = {k: md.name for k, md in meta.items()}
        m = tr._DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            scope = {k: scope_of(_stats(md.stats, stat_names).get("tf_op", ""))
                     for k, md in meta.items()}
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, tr.ASYNC_LINE):
                    out = (ops if line.name == tr.OPS_LINE else async_ops)
                    out.setdefault(dev, []).extend(
                        Op(a, b, tr.op_name(names.get(e.metadata_id, "")),
                           scope.get(e.metadata_id))
                        for a, b, e in _timed(line))
                elif line.name == tr.MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        tr.Event(a, b, names.get(e.metadata_id, ""))
                        for a, b, e in _timed(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for a, b, e in _timed(line):
                    ev = tr.Event(a, b, names.get(e.metadata_id, ""))
                    if ev.name.startswith(tr.SPAN_PREFIX):
                        spans.append(ev)
                        continue
                    host.append(ev)
                    if ev.name.startswith(PROGRAM_PREFIX):
                        program.append((ev, _stats(e.stats, stat_names)))
    for d in (ops, modules, async_ops):
        for evs in d.values():
            evs.sort(key=lambda e: (e.start, -e.end))
    spans.sort(key=lambda e: e.start)
    program.sort(key=lambda p: p[0].start)
    return Profile(tr.Trace(ops, modules, spans, host, async_ops), program)


def from_facts(facts: dict) -> Profile | None:
    """The traced run's profile with its scopes, read once per run (kept
    in ``facts``) from ``facts["xplane"]`` or else the file the harness's
    capture wrote; None when the run was not traced or left no file."""
    if facts.get("trace") is None or facts.get("window") is None:
        return None
    if "scoped_profile" not in facts:
        path = facts.get("xplane")
        try:
            if path is None:
                from bench.harness import TRACE_DIR

                path = tr.xplane_path(TRACE_DIR)
            facts["scoped_profile"] = load(path)
        except OSError:
            facts["scoped_profile"] = None
    return facts["scoped_profile"]


def window(p: Profile, facts: dict) -> tuple[int, int]:
    s = tr.span(p.trace, tr.SPAN_PREFIX + facts["window"])
    return s.start, s.end


def _leaves(evs) -> list:
    return tr.leaves([e for e in evs if e.dur > 0])


def scope_ns(p: Profile, lo: int, hi: int) -> dict:
    """Device id -> {scope: self ns of its leaf operations in [lo, hi)}
    (None collects the operations outside every scope)."""
    out = {}
    for d, evs in p.trace.ops.items():
        per = out.setdefault(d, {})
        for e in _leaves(evs):
            if e.end > lo and e.start < hi:
                ns = min(e.end, hi) - max(e.start, lo)
                per[e.scope] = per.get(e.scope, 0) + ns
    return out


def calls(p: Profile, scope: str, lo: int, hi: int) -> dict:
    """Device id -> executions that started in ``[lo, hi)`` of the
    operations of ``scope`` within a factor two of its costliest one per
    execution."""
    out = {}
    for d, evs in p.trace.ops.items():
        per = {}                                  # name -> [ns, executions]
        for e in _leaves(evs):
            if e.scope == scope and lo <= e.start < hi:
                t = per.setdefault(e.name, [0, 0])
                t[0] += e.dur
                t[1] += 1
        top = max((ns / n for ns, n in per.values()), default=0)
        out[d] = sum(n for ns, n in per.values() if 2 * ns / n >= top)
    return out


def program_spans(p: Profile, names, lo: int, hi: int) -> list:
    """``[(Event, stats)]`` of the program's host spans called one of
    ``names`` that lie in ``[lo, hi)``."""
    return [(e, st) for e, st in p.program
            if e.name in names and e.start >= lo and e.end <= hi]


def idle_inside_ns(p: Profile, names, lo: int, hi: int) -> int | None:
    """ns of ``[lo, hi)`` in which every device is idle and the host is
    inside one of the program spans ``names``; None when no such span
    lies in the window or no device ran."""
    inside = tr._union(tr._clip([e for e, _ in p.program if e.name in names],
                                lo, hi))
    if not inside or not p.trace.ops:
        return None
    busy = tr._union(tr._clip([e for evs in p.trace.ops.values()
                               for e in evs], lo, hi))
    return tr._length(inside) - tr._overlap(inside, busy)


def share_of_window(facts: dict, scope: str):
    """Percent of the traced window that the devices spent in ``scope``,
    the mean over devices; None when no operation ran in it."""
    p = from_facts(facts)
    if p is None:
        return None
    lo, hi = window(p, facts)
    per = scope_ns(p, lo, hi)
    if not any(scope in s for s in per.values()):
        return None
    return 100.0 * sum(s.get(scope, 0) for s in per.values()) / len(per) \
        / (hi - lo)


def solve_roofline(facts: dict, kernel: str, scope: str):
    """Percent of the roofline that the solve's own calls of ``kernel``
    reached: the least bytes of one call (the run's standalone kernel
    facts) times the calls, over peak bandwidth, over their device time;
    None when the run has no such facts or no such call."""
    from bench import counts

    k = (facts.get("kernels") or {}).get(kernel)
    p = from_facts(facts)
    if k is None or p is None or "peak" not in facts:
        return None
    lo, hi = window(p, facts)
    n = calls(p, scope, lo, hi)
    per = scope_ns(p, lo, hi)
    d = max(n, key=lambda dev: (n[dev], dev), default=None)
    if d is None or n[d] == 0 or not per[d].get(scope):
        return None
    share, _ = counts.roofline_share(k["flops"] * n[d], k["bytes"] * n[d],
                                     per[d][scope] / 1e9, facts["peak"])
    return share


def _allgathers(p: Profile, d, lo: int, hi: int) -> tuple[int, list]:
    """``(calls, events)`` of the all-gathers under ``repro.exchange`` that
    started on device ``d`` in ``[lo, hi)``: a call is an all-gather
    operation or the start of an async one; the events cover each from
    its start to its done."""
    def mine(evs):
        return [e for e in evs if e.scope == EXCHANGE
                and "all-gather" in e.name and lo <= e.start < hi]
    ops = mine(p.trace.ops.get(d, []))
    calls = sum("done" not in e.name for e in ops)
    return calls, ops + mine(p.trace.async_ops.get(d, []))


def exchange_roofline(facts: dict):
    """Percent of the interconnect's peak that the solve's all-gathers of
    the values reached on the device that ran the most: the least bytes
    one chip receives per all-gather times the calls, over the peak, over
    their device time (as ``trace.exposed_collective_ns`` counts it);
    None when the run has no such facts or no all-gather."""
    from bench import counts

    ex = facts.get("exchange")
    p = from_facts(facts)
    if ex is None or p is None or "peak" not in facts:
        return None
    lo, hi = window(p, facts)
    per = {d: _allgathers(p, d, lo, hi) for d in p.trace.ops}
    d = max(per, key=lambda dev: (per[dev][0], dev), default=None)
    if d is None or per[d][0] == 0:
        return None
    calls, evs = per[d]
    share, _ = counts.roofline_share(0, ex["bytes"] * calls,
                                     tr.covered_ns(evs, lo, hi) / 1e9,
                                     facts["peak"], link="ici")
    return share


def exposed_share(facts: dict, scope: str):
    """Percent of the traced window in which a collective of ``scope`` ran
    on a device (an operation, or an async one from its start to its done)
    and no other operation ran there, the mean over devices; None when no
    such collective ran."""
    p = from_facts(facts)
    if p is None:
        return None
    lo, hi = window(p, facts)

    def mine(e):
        return e.scope == scope

    if not any(mine(e) and tr.is_collective(e.name)
               and e.end > lo and e.start < hi
               for line in (p.trace.ops, p.trace.async_ops)
               for evs in line.values() for e in evs):
        return None
    exposed = tr.exposed_collective_ns(p.trace, lo, hi, counted=mine)
    return 100.0 * sum(exposed.values()) / len(exposed) / (hi - lo)
