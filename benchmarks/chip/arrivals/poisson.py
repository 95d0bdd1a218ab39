"""Open-loop Poisson arrivals at the cell's ``rate_rps``: exponential
gaps at the stratified quantiles.  Arrivals run on past the window, so
the queue never drains because the generator stopped: the window ends at
the first round boundary after its length, which can lie a prefill
round later."""

import numpy as np

MARGIN_S = 10.0


def count(mix: dict, rate_rps, seconds: float) -> int:
    if not rate_rps or rate_rps <= 0:
        raise ValueError("poisson arrivals need the cell's rate_rps")
    return int(rate_rps * (seconds + MARGIN_S)) + 1


def times_ms(mix: dict, rate_rps, q: np.ndarray) -> np.ndarray:
    return np.cumsum(-np.log1p(-q) * 1e3 / rate_rps)
