"""Median gap between step completions in the window, in ms."""

import statistics


def read(run, args):
    train = run.get("train")
    if not train or len(train["step_ends"]) < 2:
        return None
    ends = [train["t_open"]] + list(train["step_ends"])
    return statistics.median(b - a for a, b in zip(ends, ends[1:])) * 1e3
