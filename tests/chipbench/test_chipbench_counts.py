"""chipbench/counts against hand-worked shapes, and the peaks table."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run as runlib  # noqa: E402
from chipbench.counts import flash_attention, paged_decode, transformer  # noqa: E402

SERVE = json.loads((ROOT / "chipbench/configs/starcoder2-3b.json").read_text())
TRAIN = json.loads(
    (ROOT / "chipbench/configs/starcoder2-3b-train.json").read_text())


@pytest.mark.parametrize("fn,flops,nbytes", [
    # T 4096, 24 heads over 2 kv heads of 128, bf16: 2 T^2 Dh H; q, o, k, v
    # twice over plus the float32 log-sum-exp
    (flash_attention.fwd, 103_079_215_104, 54_919_168),
    # four matmuls over the triangle; q k v o do read, dq dk dv written
    (flash_attention.bwd, 206_158_430_208, 109_445_120),
])
def test_flash_causal(fn, flops, nbytes):
    assert fn(4096, 24, 2, 128) == (flops, nbytes)


def test_paged_decode_gqa_reads_kv_heads_not_query_heads():
    # two rows at contexts 1000 and 24: 4 c Dh H operations, the cache
    # read once at 2 kv heads, q and o at 24 heads
    assert paged_decode.step([1000, 24], 24, 2, 128) == (12_582_912,
                                                          1_073_152)


def test_matmul_parameters():
    assert transformer.matmul_params(TRAIN) == 534_773_760
    assert transformer.matmul_params(TRAIN, head=False) == 4 * 95_944_704


def test_train_step_is_3_5_gflop_a_token():
    # 6 x 535 M matmul parameters + 3 x causal attention at T = 4096
    assert transformer.train_flops_token(TRAIN, 4096) == 3_510_632_448


def test_prefill_and_decode_model_flops():
    assert transformer.prefill_flops(SERVE, 1000) == 3_960_970_149_888
    assert transformer.decode_flops(SERVE, 1000) == 4_385_538_048


def test_unknown_device_kind_is_an_error_not_a_default():
    assert runlib.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(runlib.Refused):
        runlib.load_peaks("TPU v9 imaginary")


def test_window_alignment_of_admissions_and_decode_steps():
    from chipbench.counts import window
    facts = {"trace_host_window": (10.0, 20.0),
             "admissions": [(9.0, 512, 300), (11.0, 1024, 700),
                            (12.0, 512, 100), (25.0, 2048, 1500)],
             # prompt 100: prefill token at 10.5, then chunks read back at
             # 11.0 (2 tokens) and 21.0 (outside)
             "token_instants": [(100, [10.5, 11.0, 11.0, 21.0]),
                                (50, [10.9, 11.0])]}
    assert window.admissions_traced(facts, 2) == [(11.0, 1024, 700),
                                                  (12.0, 512, 100)]
    # one chunk, two steps: step 0 holds both rows, step 1 the first alone
    assert sorted(window.decode_steps_traced(facts)) == [[101, 51], [102]]
