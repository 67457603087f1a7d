"""Plain reference of the toy glass: the 2-D ±J Edwards-Anderson energy
``E = -sum J_xy s_x s_y`` of a periodic lattice under its bond planes
(``jr`` to the right neighbour, ``jd`` to the one below), in numpy."""
from __future__ import annotations

import numpy as np


def energy(spins, jr, jd):
    s = np.asarray(spins, np.float64)
    return -(np.sum(jr * s * np.roll(s, -1, axis=-1), axis=(-2, -1))
             + np.sum(jd * s * np.roll(s, -1, axis=-2), axis=(-2, -1)))
