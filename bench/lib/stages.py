"""Device time by program stage, and device idle time by host span.

An addition to :mod:`bench.lib.trace`, for what its reduction does not
read.  The program wraps each stage of a solve in ``jax.named_scope``
(``voronoi``, ``distance_graph``, ``mst``, ``extract``; ``exchange``
nests inside ``voronoi`` on a mesh).  The scope is not in an ``XLA Ops``
event's name: it is in the ``tf_op`` stat of the event's metadata in the
``.xplane.pb`` (``jit(_exec_single_coo)/jit(_voronoi_cells)/voronoi/while/
body/scatter-min:``), which ``jax.profiler.ProfileData`` does not expose.
So this module reads the file itself, with a small protobuf wire-format
reader (TensorFlow's XPlane message classes are not needed).

An operation belongs to the first stage named on its ``tf_op`` path (as
itself or vmapped, ``vmap(voronoi)``), or to ``other``.  A ``while`` carries
no ``tf_op``: it takes the stage of the operations nested in it (the one
with the most time), so the loop's own overhead lands in the stage it
drives.  The few operations whose metadata XLA dropped take the stage of
the operation before them: on the TPU these are the kCustom scatter
fusions of a vmapped scatter-min, whose outer fusion carries no
``op_name`` (G'1's three scatter-mins in the batch executable, checked
against its compiled HLO).  That fallback is reported apart
(:func:`fallback_seconds`, :func:`fallback_ops`), so a share of the busy
time can be stated with and without it.  Seconds are own seconds
(:func:`bench.lib.trace.self_times`) inside the harness's ``window``.

Device idle time is put down to the innermost host annotation that covers
it: the harness's phases and the program's spans, which the program also
enters as profiler annotations while tracing (``repro.obs``).
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from bench.lib import trace

STAGES = ("voronoi", "distance_graph", "mst", "extract")
TF_OP = "tf_op"


@dataclasses.dataclass
class XPlane:
    name: str
    lines: dict  # line name -> [trace.Event]
    tf_op: dict  # event name -> tf_op path, for events whose metadata has one


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field, value)`` of one message in ``buf[lo:hi]``: an int for a
    varint, ``(start, end)`` for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span):
    """The value (field 2) of one protobuf map entry."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _plane(buf: bytes, lo: int, hi: int) -> XPlane:
    # XPlane: name 2, lines 3, event_metadata 4 (map), stat_metadata 5 (map)
    name, lines, metas, stat_names = "", [], [], {}
    for f, v in _fields(buf, lo, hi):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(_map_values(buf, v))
        elif f == 5:
            m = _map_values(buf, v)
            sid, sname = 0, ""
            for f2, v2 in _fields(buf, *m):
                if f2 == 1:
                    sid = v2
                elif f2 == 2:
                    sname = _str(buf, v2)
            stat_names[sid] = sname
    tf_op_id = next((k for k, s in stat_names.items() if s == TF_OP), None)
    # XEventMetadata: id 1, name 2, stats 5 (XStat: metadata_id 1, str_value 5,
    # ref_value 7 = the id of a stat metadata whose name is the string)
    names, tf_op = {}, {}
    for m in metas:
        mid, mname, op = 0, "", None
        for f, v in _fields(buf, *m):
            if f == 1:
                mid = v
            elif f == 2:
                mname = _str(buf, v)
            elif f == 5 and tf_op_id is not None:
                stat = dict(_fields(buf, *v))
                if stat.get(1) == tf_op_id:
                    if 5 in stat:
                        op = _str(buf, stat[5])
                    elif 7 in stat:
                        op = stat_names.get(stat[7])
        names[mid] = mname
        if op:
            tf_op[mname] = op
    # XLine: name 2, timestamp_ns 3, events 4 (XEvent: metadata_id 1,
    # offset_ps 2, duration_ps 3)
    out_lines: dict = {}
    for span in lines:
        lname, t0, evs = "", 0, []
        for f, v in _fields(buf, *span):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                t0 = _signed(v)
            elif f == 4:
                mid = off = dur = 0
                for f2, v2 in _fields(buf, *v):
                    if f2 == 1:
                        mid = v2
                    elif f2 == 2:
                        off = _signed(v2)
                    elif f2 == 3:
                        dur = v2
                evs.append((mid, off, dur))
        out_lines.setdefault(lname, []).extend(
            trace.Event(names.get(mid, ""), (t0 * 1000 + off) * 1e-12, dur * 1e-12)
            for mid, off, dur in evs)
    return XPlane(name, out_lines, tf_op)


def load(path) -> list:
    """The planes of one ``.xplane.pb`` (an XSpace: planes are field 1)."""
    buf = Path(path).read_bytes()
    return [_plane(buf, *v) for f, v in _fields(buf, 0, len(buf)) if f == 1]


def stage_of(tf_op: str | None) -> str | None:
    """The first stage named on a ``tf_op`` path; ``other`` when none is,
    None for no path."""
    if not tf_op:
        return None
    for part in tf_op.rstrip(":").split("/"):
        part = part.split("(")[-1].rstrip(")")  # vmap(voronoi) -> voronoi
        if part in STAGES:
            return part
    return "other"


def _window(planes):
    host = [e for p in planes if not trace.DEVICE_PLANE.match(p.name)
            for evs in p.lines.values() for e in evs if e.name == trace.WINDOW]
    if not host:
        raise ValueError("the trace holds no 'window' annotation")
    w = max(host, key=lambda e: e.dur)
    return w.start, w.start + w.dur


def _devices(planes, chips):
    devs = sorted((p for p in planes if trace.DEVICE_PLANE.match(p.name)),
                  key=lambda p: int(trace.DEVICE_PLANE.match(p.name).group(1)))
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    return devs[:chips]


def _assign(plane: XPlane) -> tuple:
    """``(stage, how)`` of each of the plane's operations, ``how`` being
    ``scope`` (its own ``tf_op``), ``loop`` or ``fallback``.  An operation
    without a ``tf_op`` that others nest in (a ``while``) takes the stage
    of the nested operations with the most time (``loop``); one that nests
    nothing (XLA drops the metadata of some rewritten operations, such as
    a vmapped scatter) takes the stage of the operation before it in the
    same executable, ``other`` for the first (``fallback``)."""
    evs = plane.lines.get(trace.OPS_LINE, [])
    stage = [stage_of(plane.tf_op.get(e.name)) for e in evs]
    how = ["scope" if st is not None else "fallback" for st in stage]
    order = sorted(range(len(evs)), key=lambda i: (evs[i].start, -evs[i].dur))
    votes: dict = {}
    parents: set = set()
    stack: list = []
    for i in order:
        s = evs[i].start
        while stack and evs[stack[-1]].start + evs[stack[-1]].dur <= s:
            stack.pop()
        if stack:
            parents.add(stack[-1])
        if stage[i] is not None:
            for j in stack:
                if stage[j] is None:
                    v = votes.setdefault(j, {})
                    v[stage[i]] = v.get(stage[i], 0.0) + evs[i].dur
        stack.append(i)
    for j, v in votes.items():
        stage[j], how[j] = max(v, key=v.get), "loop"
    modules = sorted((e.start, e.start + e.dur) for e in plane.lines.get(trace.MODULES_LINE, []))
    m, prev = -1, None
    for i in order:
        s = evs[i].start
        k = m
        while k + 1 < len(modules) and modules[k + 1][0] <= s:
            k += 1
        if k != m:
            m, prev = k, None
        if i not in parents:
            if stage[i] is None:
                stage[i] = prev
            else:
                prev = stage[i]
    return [st or "other" for st in stage], how


def _staged(plane: XPlane) -> list:
    """The plane's operations renamed by stage (:func:`_assign`)."""
    evs = plane.lines.get(trace.OPS_LINE, [])
    return [trace.Event(st, e.start, e.dur) for e, st in zip(evs, _assign(plane)[0])]


def assignments(planes: list, chips: int | None = None) -> list:
    """``(operation, stage, how, own seconds inside the window)`` for every
    operation of the first ``chips`` devices (:func:`_assign`)."""
    lo, hi = _window(planes)
    out = []
    for p in _devices(planes, chips):
        evs = p.lines.get(trace.OPS_LINE, [])
        own = trace.self_times([trace.Event(str(i), e.start, e.dur)
                                for i, e in enumerate(evs)], lo, hi)
        out.extend((trace.op_name(e.name), st, how, own[str(i)])
                   for i, (e, st, how) in enumerate(zip(evs, *_assign(p)))
                   if str(i) in own)
    return out


def stage_seconds(planes: list, chips: int | None = None) -> dict:
    """Stage -> device own seconds inside the window, summed over the first
    ``chips`` devices (all, by default)."""
    out: dict = {}
    for _, st, _, sec in assignments(planes, chips):
        out[st] = out.get(st, 0.0) + sec
    return out


def fallback_seconds(planes: list, chips: int | None = None) -> dict:
    """The part of :func:`stage_seconds` that the fallback assigned: stage ->
    own seconds of operations that carry no ``tf_op`` and nest nothing."""
    out: dict = {}
    for _, st, how, sec in assignments(planes, chips):
        if how == "fallback":
            out[st] = out.get(st, 0.0) + sec
    return out


def fallback_ops(planes: list, chips: int | None = None) -> dict:
    """``"<operation> -> <stage>"`` -> own seconds of the operations the
    fallback assigned, the longest first."""
    out: dict = {}
    for op, st, how, sec in assignments(planes, chips):
        if how == "fallback":
            key = f"{op} -> {st}"
            out[key] = out.get(key, 0.0) + sec
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def _innermost(spans) -> list:
    """``[(start, end, name)]``: the timeline cut where annotations begin or
    end, each piece named by the innermost (latest-opened) annotation that
    covers it; pieces no annotation covers are left out."""
    spans = sorted((x for x in spans if x[2] > x[1]), key=lambda x: (x[1], -x[2]))
    opens: dict = {}
    closes: dict = {}
    for k, (_, s, e) in enumerate(spans):
        opens.setdefault(s, []).append(k)
        closes.setdefault(e, []).append(k)
    points = sorted(opens.keys() | closes.keys())
    stack: list = []
    out = []
    for a, b in zip(points, points[1:]):
        for k in closes.get(a, ()):
            stack.remove(k)
        stack.extend(opens.get(a, ()))
        if stack:
            out.append((a, b, spans[stack[-1]][0]))
    return out


def idle_by_span(planes: list, names) -> dict:
    """Seconds in which the first device ran nothing inside the window, by
    the innermost host annotation among ``names`` that covers them
    (``other`` where none does)."""
    lo, hi = _window(planes)
    names = set(names) - {trace.WINDOW}
    dev = _devices(planes, 1)[0]
    merged = trace._union(trace._clip(dev.lines.get(trace.OPS_LINE, []), lo, hi))
    idle = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
    idle = idle[idle[:, 1] > idle[:, 0]]
    spans = [(e.name, max(e.start, lo), min(e.start + e.dur, hi))
             for p in planes if not trace.DEVICE_PLANE.match(p.name)
             for evs in p.lines.values() for e in evs
             if e.name in names and e.start < hi and e.start + e.dur > lo]
    out: dict = {}
    segs = _innermost(spans)
    j = 0
    for s, e in idle.tolist():
        covered = 0.0
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            o = min(e, segs[k][1]) - max(s, segs[k][0])
            if o > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + o
                covered += o
            k += 1
        if e - s - covered > 0:
            out["other"] = out.get("other", 0.0) + (e - s - covered)
    return out
