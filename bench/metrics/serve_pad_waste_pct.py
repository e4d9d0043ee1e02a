"""Share of launched lanes that were padding: the change over the window of
the server's ``serve_lanes_padded_total`` over that of
``serve_lanes_run_total``."""


def read(run):
    lanes = run.counters.get("serve_lanes_run_total", 0)
    if not lanes:
        return None
    return 100.0 * run.counters["serve_lanes_padded_total"] / lanes
