"""A fixture arrival kind, added as one new file: the cell's
``rate_per_s × seconds`` sessions in bursts of ``size``, each burst due
at once, bursts evenly spaced over the window."""

import numpy as np

OPEN_LOOP = True


def dues(params: dict, load: dict, seconds: float,
         rng: np.random.Generator) -> np.ndarray:
    n = max(1, round(load["rate_per_s"] * seconds))
    size = int(params["size"])
    bursts = -(-n // size)
    return np.repeat(np.arange(bursts) * (seconds / bursts), size)[:n]
