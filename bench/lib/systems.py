"""The system under test, built from a configuration's ``system`` entry.

This is the only module of the benchmark that imports the program.  It
uses the program's public entry points and nothing else: the solver
(``SteinerSolver(cfg).prepare(g).solve(seeds)``) and the server
(``SteinerServer(g, ServeConfig(...))`` with ``submit``/``flush``), and
reads the counters and spans the program keeps.
"""

from __future__ import annotations

import numpy as np

from bench.lib.check import HostTree


def build(config: dict, src, dst, w, n):
    kind = config["system"]
    if kind == "solver":
        return Solver(config, src, dst, w, n)
    if kind == "server":
        return Server(config, src, dst, w, n)
    raise ValueError(f"unknown system {kind!r}")


def _graph(config, src, dst, w, n):
    from repro.core import from_edges

    return from_edges(src, dst, w, n, pad_to=int(config["pad_to"]))


class Solver:
    """One prepared solver handle; one query per call."""

    def __init__(self, config, src, dst, w, n):
        from repro.solver import SolverConfig, SteinerSolver

        kw = {"backend": config["backend"]}
        if "mesh_shape" in config:
            kw["mesh_shape"] = tuple(config["mesh_shape"])
        self.handle = SteinerSolver(SolverConfig(**kw)).prepare(_graph(config, src, dst, w, n))

    def solve(self, seeds):
        return self.handle.solve(seeds)

    @staticmethod
    def fetch(out, seeds) -> HostTree:
        """The whole answer on the host: Voronoi state and tree."""
        import jax

        raw = out.raw
        if hasattr(raw, "state"):  # one device: arrays still on it
            st, tr = raw.state, raw.tree
            raw = jax.device_get(dict(
                dist=st.dist, lab=st.lab, pred=st.pred, path_edge=tr.path_edge,
                bridge_u=tr.bridge_u, bridge_v=tr.bridge_v, bridge_w=tr.bridge_w,
                bridge_valid=tr.bridge_valid))
        else:  # the mesh engine returns host arrays
            raw = {k: getattr(raw, k) for k in (
                "dist", "lab", "pred", "path_edge", "bridge_u", "bridge_v",
                "bridge_w", "bridge_valid")}
        return HostTree(seeds=np.asarray(seeds), total=float(out.total_distance),
                        num_edges=int(out.num_edges),
                        **{k: np.asarray(v) for k, v in raw.items()})

    @staticmethod
    def rounds(out) -> int:
        return int(out.telemetry.iterations)

    def close(self):
        self.handle = None


class Server:
    """The serving engine; the harness is its front end."""

    def __init__(self, config, src, dst, w, n):
        from repro.serve import ServeConfig, SteinerServer

        self.server = SteinerServer(_graph(config, src, dst, w, n),
                                    ServeConfig(**config.get("serve_config", {})))

    def warmup(self):
        self.server.warmup()

    def submit(self, seeds) -> int:
        return self.server.submit(seeds)

    def pending(self) -> int:
        return self.server.pending()

    def flush(self) -> dict:
        """``{ticket: (canonical seeds, total, num_edges)}``"""
        return {t: (np.asarray(r.key), float(r.total_distance), int(r.num_edges))
                for t, r in self.server.flush().items()}

    def counters(self) -> dict:
        st = self.server.stats()
        return {"serve_lanes_run_total": st["lanes_run"],
                "serve_lanes_padded_total": st["lanes_padded"]}

    def close(self):
        self.server = None


def record_program_spans(on: bool) -> None:
    """Turns the program's span recorder (``repro.obs``) on or off."""
    from repro import obs

    if on:
        obs.reset()
        obs.enable(trace=True, metrics=False)
    else:
        obs.disable()


def program_spans() -> list:
    """``[(name, seconds)]`` of the program's spans recorded while on."""
    from repro import obs

    tr = obs.tracer()
    if tr is None:
        return []
    return [(e["name"], e["dur"] * 1e-6) for e in tr.events() if e.get("ph") == "X"]
