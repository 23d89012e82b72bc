"""Faults planted under the harness: the program's answers altered,
halved, left stale or dropped where they are produced."""
import dataclasses

import jax.numpy as jnp
import numpy as np


def map_value(value, fn):
    """``value`` (Table, ArrayObject) with ``fn`` applied to every
    column."""
    if hasattr(value, "columns"):
        return dataclasses.replace(
            value, columns={k: fn(k, v) for k, v in value.columns.items()})
    return dataclasses.replace(
        value, attrs={k: fn(k, v) for k, v in value.attrs.items()})


def altered(k, v):
    """One answer changed where it is produced."""
    a = np.array(v)
    if a.dtype.kind == "f":
        a.reshape(-1)[0] = a.reshape(-1)[0] * 1.01 + 1.0
    else:
        a.reshape(-1)[0] += 1
    return jnp.asarray(a)


def halved(k, v):
    """Half of the rows left out (a single value: halved)."""
    a = np.asarray(v)
    if a.shape and a.shape[0] > 1:
        return jnp.asarray(a[: a.shape[0] // 2])
    return jnp.asarray(a * 0.5 if a.dtype.kind == "f" else a // 2)


class Stale:
    """The previous answer returned again: a step that leaves its state
    unchanged."""

    def __init__(self):
        self.last = {}

    def __call__(self, key, value):
        prev = self.last.get(key, value)
        self.last[key] = value
        return prev
