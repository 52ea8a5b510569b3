"""Simulated rounds completed in the window over the window's seconds
(host clock; the window ends with the first whole chunk past the run
length, so every round counted is in it)."""


def read(run):
    return run.rounds / run.window_s
