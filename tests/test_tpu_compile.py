"""Rehearsal compiles for a TPU v5e with no chip attached: the main
path's kernels and jitted programs at the sizes chip_smoke.py runs,
lowered and compiled by the TPU compiler installed with jax.  Nothing
runs, so these say nothing about results or time; they catch what the
chip's compiler refuses (unsupported lowerings, misaligned tiles, a
program that does not fit the 16 GB of HBM) at no chip time.

The topology is described only inside the module fixture: describing
it loads the TPU library, which one process at a time may hold."""
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9                     # one v5e chip

_SMOKE = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def topo():
    # the persistent compile cache could be written but never read back
    # without a chip; the scoped switch keeps these compiles out of it
    from jax._src import config as jax_config
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    with jax_config.enable_compilation_cache(False):
        compilation_cache.reset_cache()
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                   # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sizes():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.CHIP_SIZES


def _on(sharding, x):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                sharding=sharding)


def test_quant_cast_kernel_compiles(one_chip, sizes):
    from repro.kernels.quant_cast import quant_cast as k
    # the waveform of phase (a) as quant-cast tiles: (cells / 128, 128)
    cells = 8 * sizes["batch"]["wave_len"]
    nb = cells // k.BLOCK
    x = jax.ShapeDtypeStruct((nb, k.BLOCK), jnp.float32, sharding=one_chip)
    q = k.quantize_2d.lower(x, interpret=False).compile()
    assert "tpu_custom_call" in q.as_text()
    qs = jax.ShapeDtypeStruct((nb, k.BLOCK), jnp.int8, sharding=one_chip)
    sc = jax.ShapeDtypeStruct((nb, 1), jnp.float32, sharding=one_chip)
    d = k.dequantize_2d.lower(qs, sc, interpret=False).compile()
    assert "tpu_custom_call" in d.as_text()


@pytest.mark.parametrize("plan", ["tumbling", "sliding", "rows",
                                  "join_bounds", "join_gather"])
def test_stream_plan_compiles(plan, one_chip, sizes):
    from repro.stream import compile as qc
    hz = sizes["standing"]["hz"]
    capacity, w, half = hz * 64, hz // 2, hz // 4   # the ABP/ECG ring
    pad = qc._pow2(w)
    on = lambda x: _on(one_chip, x)                  # noqa: E731
    with jax.enable_x64(True):
        ring = np.zeros((2, capacity))
        keys = np.zeros(pad)
        if plan == "tumbling":
            lowered = qc._jit_tumbling.lower(
                on(ring), on(np.int64(0)), size=w)
        elif plan == "sliding":
            lowered = qc._jit_sliding.lower(
                on(ring), size=w, slide=half,
                max_windows=(capacity - w) // half + 1)
        elif plan == "rows":
            lowered = qc._jit_rows.lower(
                on(ring), on(np.int64(0)), length=pad)
        elif plan == "join_bounds":
            lowered = qc._jit_join_bounds.lower(on(keys), on(keys),
                                                on(np.float64(0.5)))
        else:
            lo = np.zeros(w, np.int32)
            lowered = qc._jit_join_gather.lower(
                on(np.zeros((2, pad))), on(np.zeros((2, pad))), on(keys),
                on(keys), on(lo), on(np.cumsum(lo)), on(np.zeros(pad, int)),
                pairs=pad)
        lowered.compile()


def test_qwen2_forward_fits_one_chip(one_chip, sizes):
    from repro.models import registry
    from repro.sharding import logical as L
    arch, rows = sizes["bdml"]["arch"], sizes["bdml"]["rows"]
    cfg = registry.get_config(arch)                   # published widths
    params = jax.eval_shape(
        lambda: L.init_params(jax.random.PRNGKey(0),
                              registry.param_specs(cfg)))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), params)
    toks = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=one_chip)
    fwd = jax.jit(lambda p, t: registry.forward(p, {"tokens": t}, cfg,
                                                None)[0])
    mem = fwd.lower(params, toks).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_mamba_scan_kernel_compiles_at_the_cell_shape(one_chip):
    """One Mamba layer of the icu-jamba2 scorer: S 2048, Di 5120, N 16,
    bfloat16 u/dt/B/C, the kernel's own BD and chunk."""
    from repro.kernels.mamba_scan import mamba_scan as k
    s, di, n = 2048, 5120, 16
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(    # noqa: E731
        shape, dtype, sharding=one_chip)
    compiled = k.selective_scan_chunked.lower(
        sd((1, s, di), jnp.bfloat16), sd((1, s, di), jnp.bfloat16),
        sd((di, n), jnp.float32), sd((1, s, n), jnp.bfloat16),
        sd((1, s, n), jnp.bfloat16), sd((1, di, n), jnp.float32),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jamba2_forward_fits_one_chip(one_chip, monkeypatch):
    """The icu-jamba2 cell's scorer: AI21-Jamba2-3B at published widths,
    bfloat16 weights as the ml island holds them, one (1, 2048) window,
    its 13 Mamba layers a scan period on the Pallas kernel, as on a TPU
    (the trace here sees the CPU, so the test names the platform)."""
    from repro.kernels.mamba_scan import ops
    from repro.models import registry
    from repro.stream import ml
    monkeypatch.setattr(ops, "interpret_mode", lambda x: False)
    cfg = registry.get_config("jamba2-3b")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        ml.weight_specs(cfg),
        is_leaf=lambda x: hasattr(x, "struct"))
    assert {p.dtype for p in jax.tree.leaves(params)} == \
        {jnp.dtype(jnp.bfloat16)}
    toks = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)
    fwd = jax.jit(lambda p, t: registry.forward(p, {"tokens": t}, cfg,
                                                None)[0])
    compiled = fwd.lower(params, toks).compile()
    assert compiled.as_text().count("tpu_custom_call") == 13
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6e9          # the 6.06 GB weights
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("agg", ["count", "avg", "max"])
def test_array_filter_aggregate_reads_the_waveform_once(agg, one_chip):
    from repro.core import datamodel as dm
    # the batch waveform: 10 bed-days of 8 leads at 125 Hz
    wave = jax.ShapeDtypeStruct((80, 10_800_000), jnp.float32,
                                sharding=one_chip)
    x = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = dm._filter_reduce.lower(agg, ((">", "attr", 0),), (wave,),
                                       None, (x,)).compile()
    mem = compiled.memory_analysis()
    # a mask (a quarter of the input) or a selected copy (all of it)
    # would be a temporary of the input's order
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes / 1000
    assert compiled.as_text().count(" fusion(") == 1
