"""A standing backlog: ``backlog_requests`` requests, every one due at
the window's start, so the queue never runs dry."""

import numpy as np


def count(mix: dict, rate_rps, seconds: float) -> int:
    return int(mix["backlog_requests"])


def times_ms(mix: dict, rate_rps, q: np.ndarray) -> np.ndarray:
    return np.zeros_like(q)
