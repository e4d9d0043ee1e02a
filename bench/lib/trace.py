"""Reduction of a profiler trace to the device numbers the metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  A device is a plane
named ``/device:TPU:<i>``; its operations are the events of its ``XLA Ops``
line (named by the HLO instruction, ``%fusion.12 = ...``; a ``while``
spans the operations of its body), its asynchronous transfers those of its
``Async XLA Ops`` line, its executables those of its ``XLA Modules`` line
(``jit_<name>(<hash>)``).  The measured
window is the host annotation ``window`` that the harness puts around it;
every device interval is clipped to it.  Host phases are the harness's own
annotations (``HOST_PHASES``), on the same clock.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
HOST_PHASES = ("draw", "solve", "submit", "flush", "fetch", "wait_arrival")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
                        r"|allgather|allreduce|reducescatter|alltoall", re.IGNORECASE)


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds
    dur: float


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict  # line name -> [Event]


def load(path) -> list:
    """The planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(str(path)).planes:
        lines = {}
        for line in p.lines:
            evs = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]
            lines.setdefault(line.name, []).extend(evs)
        planes.append(Plane(p.name, lines))
    return planes


def op_name(name: str) -> str:
    """``%fusion.12 = f32[4096]{0:T(1024)} fusion(...)`` -> ``fusion.12 f32[4096]``;
    a tuple-valued operation keeps its name alone; ``jit_f(123)`` -> ``jit_f``."""
    inst, _, rest = name.partition(" = ")
    inst = inst.lstrip("%").split("(", 1)[0]
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{inst} {shape}" if shape and not shape.startswith("(") else inst


def self_times(events, lo, hi) -> dict:
    """Seconds per operation name inside ``[lo, hi]``, each operation less
    the operations nested in it (a ``while`` less its body)."""
    iv = sorted(((max(e.start, lo), min(e.start + e.dur, hi), e.name) for e in events
                 if e.start + e.dur > lo and e.start < hi), key=lambda t: (t[0], -t[1]))
    own = [e - s for s, e, _ in iv]
    stack: list = []
    for i, (s, e, _) in enumerate(iv):
        while stack and iv[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, iv[stack[-1]][1]) - s
        stack.append(i)
    out: dict = {}
    for (_, _, name), t in zip(iv, own):
        key = op_name(name)
        out[key] = out.get(key, 0.0) + t
    return out


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (k, 2) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > reach[:-1]])
    last = np.r_[first[1:] - 1, iv.shape[0] - 1]
    return np.stack([iv[first, 0], reach[last]], axis=1)


def _clip(events, lo, hi) -> np.ndarray:
    iv = np.asarray([(e.start, e.start + e.dur) for e in events], np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: list  # per device
    collective_s: list  # per device: time in which a collective ran
    ops: dict  # operation name -> own seconds, summed over devices
    modules: dict  # executable name -> seconds, summed over devices
    gaps: list  # [(seconds, host phase)] of the first device, longest first

    @property
    def devices(self) -> int:
        return len(self.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return float(np.mean(self.busy_s))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v / self.devices] for k, v in ops],
            "idle_gaps": [[phase, s] for s, phase in self.gaps[:top]],
        }


def reduce(planes: list, chips: int | None = None, top_gaps: int = 10) -> Summary:
    """The numbers of the first ``chips`` devices (all, by default)."""
    host = [e for p in planes if not DEVICE_PLANE.match(p.name)
            for evs in p.lines.values() for e in evs]
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        raise ValueError("the trace holds no 'window' annotation")
    win = max(windows, key=lambda e: e.dur)
    lo, hi = win.start, win.start + win.dur
    phases = [(e.name, e.start, e.start + e.dur) for e in host if e.name in HOST_PHASES]
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    devices = devices[:chips]
    busy, coll, ops, modules, gaps = [], [], {}, {}, []
    for i, p in enumerate(devices):
        evs = p.lines.get(OPS_LINE, [])
        merged = _union(_clip(evs, lo, hi))
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])))
        coll_evs = [e for line in (OPS_LINE, ASYNC_LINE) for e in p.lines.get(line, [])
                    if COLLECTIVE.search(op_name(e.name))]
        cm = _union(_clip(coll_evs, lo, hi))
        coll.append(float(np.sum(cm[:, 1] - cm[:, 0])))
        for k, v in self_times(evs, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v
        for e in p.lines.get(MODULES_LINE, []):
            d = min(e.start + e.dur, hi) - max(e.start, lo)
            if d > 0:
                modules[op_name(e.name)] = modules.get(op_name(e.name), 0.0) + d
        if i == 0:
            idle = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
            length = idle[:, 1] - idle[:, 0]
            longest = np.argsort(-length, kind="stable")[:top_gaps]
            gaps = [(float(length[j]), _phase(phases, *idle[j]))
                    for j in longest if length[j] > 0]
    return Summary(float(hi - lo), busy, coll, ops, modules, gaps)


def _phase(phases, s, e) -> str:
    """The host phase that overlaps ``[s, e)`` most, or ``other``."""
    best, name = 0.0, "other"
    for n, ps, pe in phases:
        o = min(e, pe) - max(s, ps)
        if o > best:
            best, name = o, n
    return name
