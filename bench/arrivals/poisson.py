"""Open-loop Poisson arrivals: the cell's ``rate_per_s`` sessions per
second over the window.  The gaps are the stratified quantiles of an
exponential, scaled so that exactly ``rate_per_s × seconds`` sessions
fall due inside the window, in an order drawn from the seed."""

import numpy as np

OPEN_LOOP = True


def dues(params: dict, load: dict, seconds: float,
         rng: np.random.Generator) -> np.ndarray:
    n = max(1, round(load["rate_per_s"] * seconds))
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    gaps *= seconds / gaps.sum()          # n arrivals in the window
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
