"""Island data models: relational Table, array-island ArrayObject, and
text-island KVTable — the three data models of BigDAWG v0.1 (§VI.A).

These are real, executable implementations on jnp arrays (CPU today, TPU
sharded under a mesh): the relational model backs the data pipeline, the
array model backs tensor state, and the KV model backs the serving cache.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import metrics

# the comparisons a filter condition may make (relational and array
# islands alike): op -> fn(field, value)
OPS = {
    ">=": lambda a, b: a >= b, "<=": lambda a, b: a <= b,
    "!=": lambda a, b: a != b, "=": lambda a, b: a == b,
    ">": lambda a, b: a > b, "<": lambda a, b: a < b,
}


# ---------------------------------------------------------------------------
# Relational island: Table (columnar, 1-D columns of equal length)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Table:
    columns: Dict[str, jax.Array]          # name -> (N,) array

    def __post_init__(self):
        lens = {v.shape[0] for v in self.columns.values()}
        assert len(lens) <= 1, f"ragged table: {lens}"

    @property
    def num_rows(self) -> int:
        if not self.columns:
            return 0
        return next(iter(self.columns.values())).shape[0]

    @property
    def fields(self) -> Tuple[str, ...]:
        return tuple(self.columns)

    def nbytes(self) -> int:
        return int(sum(np.asarray(v).nbytes for v in self.columns.values()))

    def project(self, names: Sequence[str]) -> "Table":
        return Table({n: self.columns[n] for n in names})

    def filter(self, mask: jax.Array) -> "Table":
        idx = jnp.nonzero(mask)[0]
        return Table({n: v[idx] for n, v in self.columns.items()})

    def sort_by(self, name: str, descending: bool = False) -> "Table":
        order = jnp.argsort(self.columns[name])
        if descending:
            order = order[::-1]
        return Table({n: v[order] for n, v in self.columns.items()})

    def limit(self, n: int) -> "Table":
        return Table({k: v[:n] for k, v in self.columns.items()})

    def join(self, other: "Table", left_on: str, right_on: str) -> "Table":
        """Hash-free sort-merge-ish join via broadcast equality (small N)."""
        lk = self.columns[left_on]
        rk = other.columns[right_on]
        eq = lk[:, None] == rk[None, :]
        li, ri = jnp.nonzero(eq)
        out = {n: v[li] for n, v in self.columns.items()}
        for n, v in other.columns.items():
            out[n if n not in out else f"r_{n}"] = v[ri]
        return Table(out)

    def group_agg(self, by: str, agg: str, target: str) -> "Table":
        keys = self.columns[by]
        uniq = jnp.unique(keys)
        vals = self.columns[target]
        def one(k):
            m = (keys == k)
            cnt = jnp.maximum(m.sum(), 1)
            if agg == "count":
                return m.sum()
            if agg == "sum":
                return jnp.where(m, vals, 0).sum()
            if agg == "avg":
                return jnp.where(m, vals, 0).sum() / cnt
            if agg == "min":
                return jnp.where(m, vals, jnp.inf).min()
            if agg == "max":
                return jnp.where(m, vals, -jnp.inf).max()
            raise ValueError(agg)
        agged = jax.vmap(one)(uniq)
        return Table({by: uniq, f"{agg}_{target}": agged})


# ---------------------------------------------------------------------------
# Array island: ArrayObject (dims + attributes), SciDB-flavoured
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ArrayObject:
    attrs: Dict[str, jax.Array]            # name -> array of shape dims_shape
    dim_names: Tuple[str, ...]
    valid: Optional[jax.Array] = None      # bool mask (sparse-cell emulation)
    # filter conditions not yet applied to ``valid``: (attribute or
    # dimension name, op of OPS, value); ``mask()`` applies them
    conds: Tuple[Tuple[str, str, Any], ...] = ()

    @property
    def shape(self) -> Tuple[int, ...]:
        return next(iter(self.attrs.values())).shape

    def nbytes(self) -> int:
        return int(sum(np.asarray(v).nbytes for v in self.attrs.values()))

    def mask(self) -> jax.Array:
        """The selected cells: ``valid`` (every cell where None) and
        each pending condition."""
        m = self.valid
        for lhs, op, value in self.conds:
            field = self.attrs[lhs] if lhs in self.attrs \
                else self.dim_grid(lhs)
            hit = OPS[op](field, value)
            m = hit if m is None else m & hit
        return jnp.ones(self.shape, bool) if m is None else m

    def _selection(self) -> Optional[jax.Array]:
        """``valid`` with the pending conditions applied."""
        return self.mask() if self.conds else self.valid

    def dim_grid(self, name: str) -> jax.Array:
        axis = self.dim_names.index(name)
        n = self.shape[axis]
        grid = jnp.arange(n)
        reshape = [1] * len(self.shape)
        reshape[axis] = n
        return jnp.broadcast_to(grid.reshape(reshape), self.shape)

    def project(self, names: Sequence[str]) -> "ArrayObject":
        return ArrayObject({n: self.attrs[n] for n in names},
                           self.dim_names, self._selection())

    def filter(self, lhs: str, op: str, value: Any) -> "ArrayObject":
        """Keep the cells where ``lhs op value`` (``lhs`` an attribute
        or a dimension), as a pending condition: the mask is built only
        when ``mask()`` is asked for, and ``aggregate`` evaluates it
        inside its reduction."""
        if lhs not in self.attrs and lhs not in self.dim_names:
            raise ValueError(f"unknown attr/dim {lhs!r}")
        return ArrayObject(dict(self.attrs), self.dim_names, self.valid,
                           self.conds + ((lhs, op, value),))

    def aggregate(self, agg: str, attr: str) -> "ArrayObject":
        if agg not in _AGGS:
            raise ValueError(agg)
        if self.conds:
            out = self._fused_aggregate(agg, attr)
        else:
            if self.valid is not None:
                metrics.counter(
                    "repro_array_masked_aggregates_total",
                    "array aggregates over a mask already built").inc()
            out = _reduce(agg, self.attrs[attr], self.mask())
        return ArrayObject({f"{agg}_{attr}": out[None]}, ("i",))

    def _fused_aggregate(self, agg: str, attr: str) -> jax.Array:
        """``aggregate`` over the pending conditions in one jitted
        program: the thresholds are its arguments, so conditions that
        differ only in value share one executable, and each is compared
        in the dtype the eager comparison would promote to."""
        cols = [attr]
        tests, values = [], []
        for lhs, op, value in self.conds:
            if lhs in self.attrs:
                if lhs not in cols:
                    cols.append(lhs)
                kind, at = "attr", cols.index(lhs)
                dtype = self.attrs[lhs].dtype
            else:
                kind, at = "dim", self.dim_names.index(lhs)
                dtype = jax.dtypes.canonicalize_dtype(jnp.int_)
            tests.append((op, kind, at))
            values.append(np.asarray(value, jnp.result_type(dtype, value)))
        metrics.counter("repro_array_fused_aggregates_total",
                        "array filter+aggregates run as one fused "
                        "program").inc()
        return _filter_reduce(agg, tuple(tests),
                              tuple(self.attrs[n] for n in cols),
                              self.valid, tuple(values))

    def redimension(self, new_shape: Tuple[int, ...],
                    new_dims: Tuple[str, ...]) -> "ArrayObject":
        attrs = {n: v.reshape(new_shape) for n, v in self.attrs.items()}
        valid = self._selection()
        valid = None if valid is None else valid.reshape(new_shape)
        return ArrayObject(attrs, new_dims, valid)

    def sort(self, attr: str) -> "ArrayObject":
        flat = self.attrs[attr].reshape(-1)
        order = jnp.argsort(flat)
        attrs = {n: v.reshape(-1)[order] for n, v in self.attrs.items()}
        valid = self._selection()
        valid = None if valid is None else valid.reshape(-1)[order]
        return ArrayObject(attrs, ("i",), valid)

    def cross_join(self, other: "ArrayObject") -> "ArrayObject":
        """Cartesian combine over flattened cells (small arrays only)."""
        a = {n: v.reshape(-1) for n, v in self.attrs.items()}
        b = {n: v.reshape(-1) for n, v in other.attrs.items()}
        na = next(iter(a.values())).shape[0]
        nb = next(iter(b.values())).shape[0]
        out = {n: jnp.repeat(v, nb) for n, v in a.items()}
        for n, v in b.items():
            out[n if n not in out else f"r_{n}"] = jnp.tile(v, na)
        return ArrayObject(out, ("i",))


_AGGS = ("count", "sum", "avg", "min", "max")


def _reduce(agg: str, v: jax.Array, m: jax.Array) -> jax.Array:
    """``agg`` of ``v`` over the cells where ``m``; an empty selection
    counts 0, averages 0 and gives -inf / +inf as its max / min."""
    cnt = jnp.maximum(m.sum(), 1)
    if agg == "count":
        return m.sum()
    if agg == "sum":
        return jnp.where(m, v, 0).sum()
    if agg == "avg":
        return jnp.where(m, v, 0).sum() / cnt
    if agg == "min":
        return jnp.where(m, v, jnp.inf).min()
    return jnp.where(m, v, -jnp.inf).max()


@functools.partial(jax.jit, static_argnames=("agg", "tests"))
def _filter_reduce(agg: str, tests: Tuple[Tuple[str, str, int], ...],
                   cols: Tuple[jax.Array, ...], valid: Optional[jax.Array],
                   values: Tuple[jax.Array, ...]) -> jax.Array:
    """``agg`` of ``cols[0]`` over ``valid`` and the conditions
    ``tests``: (op, "attr", index into ``cols``) or (op, "dim", axis),
    each against its value.  XLA fuses the comparisons, the select and
    the reduction into one read of the inputs; no mask is stored."""
    v = cols[0]
    m = valid
    for (op, kind, at), value in zip(tests, values):
        field = cols[at] if kind == "attr" else lax.broadcasted_iota(
            jax.dtypes.canonicalize_dtype(jnp.int_), v.shape, at)
        hit = OPS[op](field, value)
        m = hit if m is None else m & hit
    return _masked_mean(v, m) if agg == "avg" else _reduce(agg, v, m)


def _masked_mean(v: jax.Array, m: jax.Array) -> jax.Array:
    """``_reduce("avg", v, m)`` with the sum and the count as one
    variadic reduction, so the two read ``v`` once (as two reductions
    XLA reads it twice).  Sum and count keep ``_reduce``'s dtypes; a
    float sum accumulates in float32 or wider."""
    s = jnp.where(m, v, 0)
    sum_dtype = jax.eval_shape(jnp.sum, s).dtype
    acc = jnp.promote_types(sum_dtype, jnp.float32) \
        if jnp.issubdtype(sum_dtype, jnp.floating) else sum_dtype
    cnt_dtype = jax.eval_shape(jnp.sum, m).dtype
    total, n = lax.reduce(
        (s.astype(acc), m.astype(cnt_dtype)),
        (np.zeros((), acc), np.zeros((), cnt_dtype)),
        lambda a, b: (a[0] + b[0], a[1] + b[1]), tuple(range(v.ndim)))
    return total.astype(sum_dtype) / jnp.maximum(n, 1)


# ---------------------------------------------------------------------------
# Text island: KVTable (Accumulo-flavoured sorted key-value rows)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class KVTable:
    """Rows sorted by key = (row, colfam, colqual); values = payloads.

    Payloads may be python strings (log-style data) or jnp arrays (KV-cache
    pages) — the engine treats them opaquely; range scans are key-based.
    """
    keys: List[Tuple[str, str, str]]
    values: List[Any]

    def __post_init__(self):
        order = sorted(range(len(self.keys)), key=lambda i: self.keys[i])
        self.keys = [self.keys[i] for i in order]
        self.values = [self.values[i] for i in order]

    @property
    def num_rows(self) -> int:
        return len(self.keys)

    def nbytes(self) -> int:
        total = 0
        for v in self.values:
            if isinstance(v, (jax.Array, np.ndarray)):
                total += int(np.asarray(v).nbytes)
            else:
                total += len(str(v))
        return total

    def scan(self) -> List[Tuple[Tuple[str, str, str], Any]]:
        return list(zip(self.keys, self.values))

    def range(self, start: Tuple[str, str, str], end: Tuple[str, str, str]
              ) -> List[Tuple[Tuple[str, str, str], Any]]:
        out = []
        for k, v in zip(self.keys, self.values):
            if (k[0] >= start[0] and k[0] <= end[0]
                    and (not start[1] or k[1] >= start[1])
                    and (not end[1] or k[1] <= end[1])):
                out.append((k, v))
        return out

    def put(self, key: Tuple[str, str, str], value: Any) -> None:
        self.keys.append(key)
        self.values.append(value)
        self.__post_init__()


def object_kind(obj: Any) -> str:
    if isinstance(obj, Table):
        return "table"
    if isinstance(obj, ArrayObject):
        return "array"
    if isinstance(obj, KVTable):
        return "kvtable"
    if isinstance(obj, (jax.Array, np.ndarray)):
        return "tensor"
    return "pytree"


def object_nbytes(obj: Any) -> int:
    if hasattr(obj, "nbytes") and callable(getattr(obj, "nbytes")):
        return int(obj.nbytes())
    if isinstance(obj, (jax.Array, np.ndarray)):
        return int(np.asarray(obj).nbytes) if isinstance(obj, np.ndarray) \
            else int(obj.size * obj.dtype.itemsize)
    leaves = jax.tree.leaves(obj)
    return int(sum(l.size * l.dtype.itemsize for l in leaves
                   if hasattr(l, "size")))
