"""Inputs of every cell, made from ``--seed``.

Copies of the MIMIC II generators of ``repro.data.mimic`` (the same
signal families and schema), kept here so the yardstick does not move
when the program's own generators do.  The program receives only what
these functions return, through its public API.

Stream feeds are made on the host (numpy), one batch at a time, because
the program ingests numpy batches; the batch polystore's tables and
waveform are made on the device in one jitted call, in the dtype the
program serves them in (float32 values, int32 ids).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def sub_seed(seed: int, *names: str) -> int:
    """A 31-bit seed derived from ``seed`` and a purpose, so one
    ``--seed`` of any size gives independent streams for the data, the
    order of the queries and the sample the check compares."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [sum(ord(c) << (8 * (i % 4)) for i, c in enumerate(n))
              for n in names]
    return int(np.random.SeedSequence(words).generate_state(1)[0]
               & 0x7FFFFFFF)


# -- the ward: one bed stream, samples of every bed at a fixed rate ----------
def ward_batch(rng: np.random.Generator, k: int, beds: int,
               samples: int, hz: int) -> Dict[str, np.ndarray]:
    """Batch ``k`` of the ward feed: ``samples`` consecutive samples of
    every bed, ordered by sample then bed.  ``t`` is the sample index,
    so a batch's rows follow the previous batch's without a gap."""
    t = k * samples + np.repeat(np.arange(samples), beds)
    bed = np.tile(np.arange(beds), samples).astype(np.float64)
    abp = (90.0 + 0.1 * bed + 12.0 * np.sin(2 * np.pi * 1.2 * t / hz)
           + 0.5 * rng.standard_normal(t.shape[0]))
    return {"t": t.astype(np.float64), "bed": bed, "abp": abp}


# -- one patient's ABP/ECG pair, out of order within a batch ------------------
def pair_batch(rng: np.random.Generator, k: int, samples: int,
               jitter: float, ecg_offset: float
               ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Batch ``k`` of the jittered ABP/ECG feed (as
    ``stream_mimic_paired_waveforms``): ``ts`` counts samples, each
    stream's rows arrive in the order of ``ts + U(-jitter, jitter)``."""
    t = k * samples + np.arange(samples, dtype=np.float64)
    order = np.argsort(t + rng.uniform(-jitter, jitter, samples),
                       kind="stable")
    abp = (90.0 + 12.0 * np.sin(2 * np.pi * t / 360.0)
           + 0.5 * rng.standard_normal(samples))[order]
    abp_rows = {"ts": t[order], "abp": abp}
    order = np.argsort(t + rng.uniform(-jitter, jitter, samples),
                       kind="stable")
    ecg = (np.sin(2 * np.pi * t / 6.0)
           + 0.1 * rng.standard_normal(samples))[order]
    ecg_rows = {"ts": (t + ecg_offset)[order], "ecg": ecg}
    return abp_rows, ecg_rows


# -- the batch polystore (load_mimic_demo's schema) --------------------------
def polystore_arrays(seed: int, *, num_patients: int, num_orders: int,
                     leads: int, bed_days: int, samples_per_day: int,
                     amplitudes):
    """Every numeric column of the batch polystore, made on the device
    in one jitted call: ``d_patients``, ``poe_order`` and the
    ``(bed_days x leads, samples_per_day)`` waveform, one row per lead of
    each bed-day.  Doses are an even grid over [0.5, 50) in the seed's
    order, and each bed-day's lead ``i`` is a sine of amplitude
    ``amplitudes[perm[i]]`` plus noise, ``perm`` drawn per bed-day: every
    seed then selects cohorts of the same sizes and filters the same
    shares of samples, so every seed runs programs of the same shapes.
    The waveform is made a row at a time, so the call needs little more
    memory than the waveform itself."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(sub_seed(seed, "leads"))
    base = np.asarray(amplitudes, np.float32)
    amp = jnp.asarray(np.concatenate([rng.permutation(base)
                                      for _ in range(bed_days)]))

    # the seed reaches the program only as arguments, so every seed runs
    # the one compiled generator
    @jax.jit
    def make(key, amp):
        ks = jax.random.split(key, 8)
        i32 = jnp.int32
        out = {
            "sex": jax.random.randint(ks[0], (num_patients,), 0, 2, i32),
            "dob_year": jax.random.randint(ks[1], (num_patients,), 1930,
                                           2000, i32),
            "expire": jax.random.randint(ks[2], (num_patients,), 0, 2, i32),
            "subject_id": jax.random.randint(ks[3], (num_orders,), 0,
                                             num_patients, i32),
            "icustay_id": jax.random.randint(ks[4], (num_orders,), 0, 512,
                                             i32),
            "dose": jax.random.permutation(
                ks[5], 0.5 + 49.5 * (jnp.arange(num_orders, dtype=jnp.float32)
                                     + 0.5) / num_orders),
        }
        wave = jnp.sin(2 * jnp.pi
                       * jnp.arange(samples_per_day, dtype=jnp.float32)
                       / 360.0)

        def row(i):
            noise = jax.random.normal(jax.random.fold_in(ks[6], i),
                                      (samples_per_day,), jnp.float32)
            return wave * amp[i] + 0.05 * noise

        out["signal"] = jax.lax.map(row, jnp.arange(bed_days * leads))
        return out

    return make(jax.random.PRNGKey(sub_seed(seed, "polystore")), amp)


def notes(seed: int, count: int) -> Tuple[list, list]:
    """The free-text notes of the key-value engine (``mimic_logs``)."""
    hr = np.random.default_rng(sub_seed(seed, "notes")).integers(
        50, 120, count)
    keys = [(f"r_{i:04d}", "note", "text") for i in range(count)]
    values = [f"synthetic clinical note {i}: pt stable, hr={int(h)}"
              for i, h in enumerate(hr)]
    return keys, values
