"""Longest cycle of the window, from the environment's stamps: a stall shows here."""


def read(run):
    return max(run.recorder.cycle_seconds()) * 1e3
