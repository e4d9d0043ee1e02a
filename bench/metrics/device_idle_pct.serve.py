"""Idle share of the device while it serves open-loop traffic."""

from bench.lib.readers import idle_pct


def read(run):
    return idle_pct(run)
