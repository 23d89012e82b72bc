"""The Migrator (paper §V.C): moves objects between engines.

Routes:
  binary — zero-copy/native handoff (the paper's PostgreSQL<->SciDB binary
           migration); cross-model objects are translated via the
           destination engine's ``coerce`` using the cast's target schema.
  staged — format-translating slow path (CSV export -> parse -> load),
           faithful to the paper's observation that cross-island migration
           pays format translation + dispatch costs.
  quant  — binary + int8 re-coding through the quant_cast Pallas kernel
           (KV-cache pages, gradient compression) — a beyond-paper cast.
  stream — live stream-state *move* between StreamEngines: the ring
           buffer's full state (data, cumulative rings, seq watermark,
           drop counters, rate history — and for event-time streams the
           insertion buffer, low watermark, and late-row counters, so
           pending out-of-order rows are neither lost nor double-
           counted) is deep-copied onto the destination and the source
           copy deleted, so a shard can be rebalanced under a running
           standing query without losing continuity.  Unlike the other
           routes this one moves rather than copies by default — two
           *writable* replicas of one append-ordered buffer would fork
           the seq space.  ``MigrationParams(copy=True)`` instead
           builds a **read replica**: the source stays live and the
           destination gets a detached, renamed deep copy for fan-out
           reads (the serving front door's hot-read path); a durable
           source's replica carries the segment-log positions captured
           at the copy instant, so ``durability.catch_up`` can replay
           it forward incrementally without a seq fork.

On a TPU mesh the binary route between DenseHBM shardings is a resharding
collective (device_put to a new NamedSharding) — no host round-trip; the
staged route stages through host memory.  Both are exercised by the
benchmarks to reproduce the paper's migration-cost structure.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import datamodel as dm
from repro.core.engines import Engine
from repro.obs import metrics, trace


class MigrationException(Exception):
    pass


@dataclasses.dataclass
class MigrationParams:
    method: Optional[str] = None        # None -> negotiate from catalog
    dest_schema: str = ""
    quant_block: int = 128
    copy: bool = False                  # stream route: replica, not move


@dataclasses.dataclass
class MigrationResult:
    object_from: str
    object_to: str
    engine_from: str
    engine_to: str
    method: str
    bytes_moved: int
    rows: int
    dispatch_seconds: float
    transfer_seconds: float

    @property
    def seconds(self) -> float:
        return self.dispatch_seconds + self.transfer_seconds


class Migrator:
    """Single static-style interface, mirroring the paper's Migrator class."""

    def __init__(self, catalog=None) -> None:
        self.catalog = catalog
        self.log: list[MigrationResult] = []

    def migrate(self, engine_from: Engine, object_from: str,
                engine_to: Engine, object_to: str,
                params: Optional[MigrationParams] = None) -> MigrationResult:
        params = params or MigrationParams()
        with trace.span("migrator/route", src=engine_from.name,
                        dst=engine_to.name) as sp:
            t0 = time.perf_counter()
            if not engine_from.has(object_from):
                raise MigrationException(
                    f"{engine_from.name} has no object {object_from!r}")
            method = params.method or self._negotiate(engine_from,
                                                      engine_to)
            sp.set(method=method)
            t1 = time.perf_counter()

            obj = engine_from.get(object_from)
            nbytes = dm.object_nbytes(obj)
            rows = getattr(obj, "num_rows", 0) or (
                int(np.prod(obj.shape))
                if isinstance(obj, dm.ArrayObject) else 0)

            if method == "binary":
                payload, schema = engine_from.export_binary(object_from)
                schema["dest_schema"] = params.dest_schema
                coerced = engine_to.coerce(payload, schema)
                engine_to.import_binary(object_to, coerced, schema)
            elif method == "staged":
                payload, schema = engine_from.export_staged(object_from)
                schema["dest_schema"] = params.dest_schema
                engine_to.import_staged(object_to, payload, schema)
            elif method == "quant":
                self._quant_migrate(engine_from, object_from, engine_to,
                                    object_to, params)
            elif method == "stream":
                self._stream_migrate(engine_from, object_from, engine_to,
                                     object_to, copy=params.copy)
            else:
                raise MigrationException(f"unknown cast method {method!r}")
            t2 = time.perf_counter()

        result = MigrationResult(
            object_from=object_from, object_to=object_to,
            engine_from=engine_from.name, engine_to=engine_to.name,
            method=method, bytes_moved=nbytes, rows=int(rows),
            dispatch_seconds=t1 - t0, transfer_seconds=t2 - t1)
        self.log.append(result)
        metrics.counter("repro_migrations_total",
                        "Migrator routes executed",
                        method=method).inc()
        metrics.counter("repro_migration_bytes_total",
                        "bytes moved between engines",
                        method=method).inc(nbytes)
        metrics.histogram("repro_migration_seconds",
                          "dispatch + transfer time per migration",
                          method=method).observe(result.seconds)
        engine_from.record(f"migrate_out:{method}", result.seconds)
        engine_to.record(f"migrate_in:{method}", result.seconds)
        return result

    def _negotiate(self, engine_from: Engine, engine_to: Engine) -> str:
        """Pick the cast route: catalog-registered, else binary."""
        if self.catalog is not None:
            src = self.catalog.engine_by_name(engine_from.name)
            dst = self.catalog.engine_by_name(engine_to.name)
            if src and dst:
                casts = self.catalog.casts_between(src.eid, dst.eid)
                if casts:
                    # prefer binary > quant > staged
                    order = {"binary": 0, "quant": 1, "staged": 2}
                    return sorted(casts,
                                  key=lambda c: order.get(c.method, 9)
                                  )[0].method
        return "binary"

    def _stream_migrate(self, engine_from: Engine, object_from: str,
                        engine_to: Engine, object_to: str,
                        copy: bool = False) -> None:
        """Move a live ring buffer between StreamEngines (see module
        docstring: this route moves by default; ``copy=True`` builds a
        detached read replica instead and leaves the source live).

        The source is a ring of a multi-ring stream (``{name}@shardN``,
        moved by ``Stream.migrate_shard``), a one-ring stream (moved
        whole), or — copy mode only — any stream.  Ring moves are safe
        under concurrent producers: ``migrate_shard`` pauses the ring's
        ordered committer, so every seq block reserved before the move
        drains into the exported state and blocks reserved during it
        publish to the new ring afterwards — in-flight reservations are
        carried, never lost.  ``Stream.export_state`` likewise drains
        its lanes first.  Only a *direct* move of a one-ring stream
        still needs external serialization: a block reserved after the
        export but before the delete below lands in the doomed source
        object (pause the feed, or move between ticks)."""
        from repro.stream.engine import StreamEngine, movable
        obj = engine_from.get(object_from)
        if not movable(obj, copy):
            raise MigrationException(
                f"stream cast needs a stream ring, a one-ring Stream or "
                f"(copying) any Stream source, got {type(obj).__name__} "
                f"for {object_from!r}")
        if not isinstance(engine_to, StreamEngine):
            raise MigrationException(
                f"stream cast needs a StreamEngine destination, "
                f"{engine_to.name} is {engine_to.kind}")
        if engine_to is engine_from and object_to == object_from:
            # moving: put + delete source would delete the freshly
            # imported copy; copying: put would overwrite the primary
            raise MigrationException(
                f"stream cast cannot {'copy' if copy else 'move'} "
                f"{object_from!r} onto itself on {engine_from.name}")
        if copy:
            durable = getattr(obj, "_durable", None)
            if durable is not None:
                # capture (state, per-lane log positions) at one
                # coherent instant so durability.catch_up can replay
                # the replica forward from exactly where the copy ends
                state, lsns = obj._checkpoint_snapshot(
                    durable.lane_lsns)
                replica = obj.clone(object_to, state=state)
                replica._replica_lsns = lsns
            else:
                replica = obj.clone(object_to)
            engine_to.put(object_to, replica)
            return
        engine_to.put(object_to, type(obj).from_state(obj.export_state()))
        engine_from.delete(object_from)
        # a move changes physical placement, so the catalog must follow
        # (copy routes leave the source object untouched and don't)
        if (self.catalog is not None
                and self.catalog.object_by_name(object_to) is not None
                and self.catalog.engine_by_name(engine_to.name)
                is not None):
            self.catalog.relocate_object(object_to, engine_to.name)

    def _quant_migrate(self, engine_from: Engine, object_from: str,
                       engine_to: Engine, object_to: str,
                       params: MigrationParams) -> None:
        from repro.kernels.quant_cast import ops as qops
        obj = engine_from.get(object_from)
        if isinstance(obj, dm.KVTable):
            keys, vals = [], []
            for k, v in obj.scan():
                if isinstance(v, (jax.Array, np.ndarray)):
                    q, scale = qops.quantize(jnp.asarray(v, jnp.float32),
                                             block=params.quant_block)
                    vals.append({"q": q, "scale": scale})
                else:
                    vals.append(v)
                keys.append(k)
            engine_to.import_binary(object_to, dm.KVTable(keys, vals),
                                    {"kind": "kvtable", "codec": "int8"})
            return
        if isinstance(obj, (jax.Array, np.ndarray)):
            q, scale = qops.quantize(jnp.asarray(obj, jnp.float32),
                                     block=params.quant_block)
            engine_to.import_binary(object_to, {"q": q, "scale": scale},
                                    {"kind": "tensor", "codec": "int8",
                                     "shape": list(np.asarray(obj).shape)})
            return
        if isinstance(obj, (dm.ArrayObject, dm.Table)):
            fields = obj.attrs if isinstance(obj, dm.ArrayObject) \
                else obj.columns
            quantized = {
                n: dict(zip(("q", "scale"),
                            qops.quantize(jnp.asarray(v, jnp.float32),
                                          block=params.quant_block)))
                for n, v in fields.items()}
            engine_to.import_binary(
                object_to, quantized,
                {"kind": dm.object_kind(obj), "codec": "int8"})
            return
        # pytree of tensors (model state objects)
        quantized = jax.tree.map(
            lambda leaf: dict(zip(("q", "scale"),
                                  qops.quantize(jnp.asarray(
                                      leaf, jnp.float32),
                                      block=params.quant_block))), obj)
        engine_to.import_binary(object_to, quantized,
                                {"kind": "pytree", "codec": "int8"})


def reshard(array: jax.Array, sharding) -> jax.Array:
    """Device-to-device binary cast between shardings (no host round-trip).

    This is the TPU-native reading of the paper's binary migration: on a
    mesh, ``device_put`` onto a new NamedSharding lowers to all-to-all /
    collective-permute traffic only.
    """
    return jax.device_put(array, sharding)
