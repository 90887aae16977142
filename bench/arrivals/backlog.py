"""An offline backlog: the cell's ``backlog`` sessions, all due at 0 and
sent before the window opens."""

import numpy as np

OPEN_LOOP = False


def dues(params: dict, load: dict, seconds: float,
         rng: np.random.Generator) -> np.ndarray:
    return np.zeros(int(load["backlog"]))
