"""Useful share of the batched while-loop's row trials (%): sum of row
trials over (rows x the most trials of a row), from the solver's own
per-row ``SolveStats.n_trials``; averaged over the batches."""

import numpy as np


def read(ctx):
    shares = []
    for trials in ctx["counters"].get("trials", []):
        t = np.asarray(trials, np.float64)
        if t.max() > 0:
            shares.append(t.sum() / (t.size * t.max()))
    return 100.0 * float(np.mean(shares)) if shares else None
