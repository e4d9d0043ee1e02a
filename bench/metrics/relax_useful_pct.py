"""Share of the relaxation's edge scan that improved a vertex: 100 times
the sum of ``relaxations`` (vertex improvements, one winning edge each,
the same in every schedule) over the sum of ``scanned`` (the edges the
round kernels read), over the queries of the window.  Both are the
program's exact per-solve totals: the ``solve_totals[...]`` counter
samples that its recorder (``repro.obs``), on for the traced window,
keeps readable after it."""


def read(run):
    from repro import obs

    tr = obs.tracer()
    if tr is None:
        return None
    totals = [e["args"] for e in tr.events() if e.get("ph") == "C"
              and e["name"].startswith("solve_totals[") and "scanned" in e["args"]]
    scanned = sum(a["scanned"] for a in totals)
    if not scanned:
        return None
    return 100.0 * sum(a["relaxations"] for a in totals) / scanned
