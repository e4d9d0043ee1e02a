"""Share of each chip's busy time in which a collective (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all) ran, mean over
the chips."""


def read(run):
    t = run.trace
    if t is None or not any(t.collective_s):
        return None
    return sum(100.0 * c / b for c, b in zip(t.collective_s, t.busy_s) if b > 0) / t.devices
