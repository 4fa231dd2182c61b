"""The benchmark's FLOP and byte counters against counts worked out by
hand for one Qwen3-8B decode step and one prefill chunk (18 layers, hidden
4096, 32 query and 8 KV heads of 128, MLP 12288, vocabulary 151936)."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(HERE))

from chipbench import counts  # noqa: E402
from chipbench.device import PEAKS, check_devices  # noqa: E402
from chipbench.weights import Dims  # noqa: E402

V5E = PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def qwen3():
    return Dims.from_config(json.loads((HERE / "configs" / "qwen3-8b-h18.json").read_text()))


def test_layer_weights(qwen3):
    # q 4096*32*128 + k, v 2*4096*8*128 + o 32*128*4096 + MLP 3*4096*12288
    assert counts.matmul_params(qwen3) == 16_777_216 + 8_388_608 + 16_777_216 + 150_994_944


def test_decode_step_flops(qwen3):
    # Two rows with 100 and 300 live tokens: per row 18 * 2 * 192,937,984
    # weight FLOPs, per token of context 18 * 4 * 32 * 128 attention FLOPs,
    # and the head 2 * 4096 * 151,936 for each row.
    want = 2 * 18 * 385_875_968 + 18 * 16_384 * 400 + 2 * 1_244_659_712
    assert counts.decode_flops(qwen3, [100, 300]) == want == 16_498_819_072


def test_chunk_flops(qwen3):
    # 128 tokens after 256 cached: they attend to 257..384 tokens, in all
    # 128 * 256 + 128 * 129 / 2 = 41,024; one head row.
    want = 128 * 18 * 385_875_968 + 18 * 16_384 * 41_024 + 1_244_659_712
    assert counts.chunk_flops(qwen3, 256, 128) == want == 902_401_359_872


def test_paged_decode_least_time(qwen3):
    # Per layer: K and V of 400 live tokens, 8 heads of 128 in bf16, plus q
    # and o for 2 rows of 32 heads; memory bound on a v5e.
    nbytes = 2 * 8 * 128 * 2 * 400 + 2 * 2 * 32 * 128 * 2
    flops = 4 * 32 * 128 * 400
    assert nbytes / 819e9 > flops / 197e12
    assert counts.paged_decode_least_s(qwen3, [100, 300], 1, V5E) == pytest.approx(nbytes / 819e9)


def test_paged_chunk_least_time(qwen3):
    # K and V of 384 tokens, q and o of 128 tokens of 32 heads.
    nbytes = 2 * 8 * 128 * 2 * 384 + 2 * 128 * 32 * 128 * 2
    assert counts.paged_chunk_least_s(qwen3, 256, 128, 1, V5E) == pytest.approx(nbytes / 819e9)
    # Split over four chips, each holds a quarter of the heads.
    assert counts.paged_chunk_least_s(qwen3, 256, 128, 4, V5E) == pytest.approx(nbytes / 4 / 819e9)


def test_device_check_refuses_the_cpu():
    import jax

    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        check_devices(jax.devices(), 1)


def test_unknown_device_has_no_peaks():
    from chipbench.device import peaks_for

    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
