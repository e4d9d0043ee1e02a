"""Host time of one ``SteinerServer.flush``: the mean ``serve:flush`` span
less the ``serve:solve`` launches nested in it (assembly, the answers'
host-side handling, the bookkeeping between launches)."""


def read(run):
    flushes = [s for name, s in run.spans if name == "serve:flush"]
    if not flushes:
        return None
    launches = sum(s for name, s in run.spans if name == "serve:solve")
    return 1e3 * (sum(flushes) - launches) / len(flushes)
