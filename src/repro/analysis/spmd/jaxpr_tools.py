"""Shared jaxpr-walking utilities for the spmd analyses.

Everything here treats jaxprs structurally: equations are dispatched on
``eqn.primitive.name`` (a stable string across jax versions), sub-jaxprs
are discovered generically in ``eqn.params`` (so new higher-order
primitives degrade to "walk inside" instead of crashing), and source
provenance comes from jax's own ``eqn.source_info`` — the same traceback
jax prints in its error messages — filtered to the first user frame.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Iterator, List, Optional, Tuple

from jax.extend import core as jax_core

from repro.analysis.findings import Finding, norm_path
from repro.analysis.suppress import suppresses

# collective primitives and where their axis names live in eqn.params
_AXES_PARAM = {
    "psum": "axes",
    "pmin": "axes",
    "pmax": "axes",
    "all_gather": "axis_name",
    "all_to_all": "axis_name",
    "reduce_scatter": "axis_name",
    "ppermute": "axis_name",
    "pbroadcast": "axes",
    "axis_index": "axis_name",
}
# collectives that *reduce* over their axes: the result is uniform along
# them (axis_index/ppermute produce or keep rank-varying values instead)
REDUCING_COLLECTIVES = frozenset(
    {"psum", "pmin", "pmax", "all_gather", "pbroadcast", "reduce_scatter"}
)
COLLECTIVES = frozenset(_AXES_PARAM) - {"axis_index"}


def collective_axes(eqn) -> Optional[Tuple[str, ...]]:
    """Axis names a collective eqn operates over; None for non-collectives.

    Normalizes the str-vs-tuple spelling difference between ``psum``-style
    (``axes``) and ``all_gather``-style (``axis_name``) primitives."""
    param = _AXES_PARAM.get(eqn.primitive.name)
    if param is None:
        return None
    axes = eqn.params.get(param)
    if axes is None:
        return ()
    if isinstance(axes, (str, int)):
        return (axes,) if isinstance(axes, str) else ()
    return tuple(a for a in axes if isinstance(a, str))


def sub_jaxprs(eqn) -> Iterator[Tuple[str, "jax_core.Jaxpr", list]]:
    """Yields ``(param_name, open_jaxpr, consts)`` for every sub-jaxpr in
    an equation's params, whatever the primitive."""
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield name, v.jaxpr, list(v.consts)
            elif isinstance(v, jax_core.Jaxpr):
                yield name, v, []


def walk_eqns(jaxpr: "jax_core.Jaxpr") -> Iterator[object]:
    """Every equation in ``jaxpr``, recursing through sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for _, sub, _consts in sub_jaxprs(eqn):
            yield from walk_eqns(sub)


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

_LINE_CACHE: Dict[str, List[str]] = {}


def _file_lines(path: str) -> List[str]:
    if path not in _LINE_CACHE:
        try:
            with open(path, "r", encoding="utf-8") as fh:
                _LINE_CACHE[path] = fh.read().splitlines()
        except OSError:
            _LINE_CACHE[path] = []
    return _LINE_CACHE[path]


def _relativize(path: str) -> str:
    """Repo-relative path when possible (stable baseline keys anywhere)."""
    p = norm_path(path)
    for anchor in ("src/repro/", "tests/"):
        idx = p.find("/" + anchor)
        if idx >= 0:
            return p[idx + 1:]
        if p.startswith(anchor):
            return p
    cwd = norm_path(os.getcwd()) + "/"
    if p.startswith(cwd):
        return p[len(cwd):]
    return p


@dataclasses.dataclass(frozen=True)
class Provenance:
    """Source attribution of one jaxpr equation."""

    path: str  # repo-relative when resolvable, "<jaxpr>" otherwise
    line: int
    line_text: str
    abs_path: str = ""


def provenance(eqn) -> Provenance:
    """Best-effort user-source location of an equation.

    Uses ``jax._src.source_info_util.user_frame`` — the same frame jax
    attributes tracing errors to — and degrades to an unlocated
    ``<jaxpr>`` pseudo-path if the API or traceback is unavailable."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        frame = None
    if frame is None:
        return Provenance(path="<jaxpr>", line=0, line_text="")
    abs_path = frame.file_name
    line = int(frame.start_line)
    lines = _file_lines(abs_path)
    text = lines[line - 1].strip() if 1 <= line <= len(lines) else ""
    return Provenance(
        path=_relativize(abs_path), line=line, line_text=text,
        abs_path=abs_path,
    )


@dataclasses.dataclass(frozen=True)
class Violation:
    """One semantic-rule violation, pre-Finding (no combo context yet)."""

    rule: str
    message: str
    eqn: object  # the jaxpr equation carrying provenance

    def to_finding(self, context: str) -> Optional[Finding]:
        """Renders against one backend/mode context; honors per-line
        ``# jitlint: ignore[...]`` comments on the attributed source line
        (None = suppressed)."""
        prov = provenance(self.eqn)
        if prov.line_text and suppresses(prov.line_text, self.rule):
            return None
        prim = getattr(getattr(self.eqn, "primitive", None), "name", "?")
        return Finding(
            rule=self.rule,
            path=prov.path,
            line=prov.line,
            col=0,
            message=f"[{prim}] {self.message}",
            context=context,
            line_text=prov.line_text,
        )
