"""What the generators share: answers brought to the host, the tail of a
latency sample, the reservoir of answers kept for the check, and the
control's rounding to the next lower precision."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def to_host(value: Any) -> Dict[str, np.ndarray]:
    """Every column of a Table, attribute of an ArrayObject, or a bare
    array, as numpy (waits for the device)."""
    cols = getattr(value, "columns", None)
    if cols is None:
        cols = getattr(value, "attrs", None)
    if cols is None:
        return {"value": np.asarray(value)}
    return {k: np.asarray(v) for k, v in cols.items()}


def p95(samples: List[float]) -> float:
    return float(np.percentile(np.asarray(samples, np.float64), 95))


def bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest even), back in float64."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown
    length, drawn from ``rng``."""

    def __init__(self, rng: np.random.Generator, size: int) -> None:
        self.rng = rng
        self.size = size
        self.seen = 0
        self.items: List[Tuple[int, Any]] = []

    def offer(self, item: Any) -> None:
        if len(self.items) < self.size:
            self.items.append((self.seen, item))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = (self.seen, item)
        self.seen += 1
