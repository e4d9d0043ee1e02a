"""Idle share of the device while it solves queries back to back."""

from bench.lib.readers import idle_pct


def read(run):
    return idle_pct(run)
