"""chip_smoke.py off the chip: its device check refuses the CPU, and its
serve phase runs end to end on the smoke-scale config."""
import importlib.util
from pathlib import Path

import jax
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(chip_smoke):
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        chip_smoke.check_platform(jax.devices())


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_serve_phase_runs_reduced(chip_smoke, backend):
    v = chip_smoke.serve_phase(backend, reduced=True)
    assert v["device"]["platform"] == jax.devices()[0].platform
    assert v["generated_tokens"] == chip_smoke.REQUESTS * chip_smoke.MAX_NEW_TOKENS
    assert v["decode_traces"] == 1
    assert v["chunk_traces"] == 1  # one chunk bucket, one page bucket
