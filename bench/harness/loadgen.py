"""The one general traffic generator: every mix is a data file it reads.

``windows`` draws float windows for a design, normal times ``scale``,
from the generator it is given, so one seed gives one dataset.
"""
from __future__ import annotations

import numpy as np


def windows(rng: np.random.Generator, n: int, shape, scale: float
            ) -> np.ndarray:
    return (rng.standard_normal((n, *shape)) * scale).astype(np.float32)
