"""chip_smoke.py's three phases at tiny sizes on the CPU: every phase's
reference checks must hold here before the script spends chip time.
``main()`` runs only to show that it refuses any platform but a TPU."""
import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_batch_phase_matches_numpy(smoke):
    out = smoke.phase_batch(num_patients=64, num_orders=512, wave_len=2048,
                            num_logs=32)
    assert out["cast_rows"] == 512
    assert out["waveform_bytes"] == 8 * 2048 * 4
    assert out["filtered_samples"] > 0


def test_standing_phase_matches_numpy(smoke):
    out = smoke.phase_standing(beds=4, hz=16, ticks=4, shards=4)
    assert out["ticks"] == 5
    assert out["deliveries"] == 5 * 11
    assert out["shared_queries"] == 10
    assert out["compile"]["fallbacks"] == 0
    assert out["compile"]["executions"] > 0


def test_bdml_phase_matches_direct_forward(smoke):
    out = smoke.phase_bdml(arch="lm", rows=16, ticks=2)
    assert out["windows_scored"] == 2
    assert out["published_config"] is False
    assert len(out["scores"]) == 2


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main() == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
