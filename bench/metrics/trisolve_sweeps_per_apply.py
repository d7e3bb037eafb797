"""Sweeps of one forward plus one backward triangular solve of the
cell's factor, counted from its sweep plan for one lane."""


def read(run):
    return run.sweeps_per_apply
