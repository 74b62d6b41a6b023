"""Work counts against values worked by hand, and the peak table."""
import json

import pytest

from bench import counts

# internlm2-1.8b at its published sizes (internlm/internlm2-1_8b config.json)
INTERNLM2 = {"num_hidden_layers": 24, "hidden_size": 2048,
             "num_attention_heads": 16, "num_key_value_heads": 8,
             "head_dim": 128, "intermediate_size": 8192, "vocab_size": 92544}


def test_risk_step_work_at_the_papers_widths():
    # one tenant of a 131,072-trial analysis on one chip: 65,536 trials,
    # 1000 events, 15 ELTs over a 2,000,000-event catalog
    flops, nbytes = counts.risk_step_work(65536, 1000, 15, 2_000_000)
    assert flops == 4 * 65536 * 1000 * 15 == 3_932_160_000
    # ids 65,536,000 x 4 + ELT 2,000,001 x 15 x 4 + YLT 65,536 x 4
    assert nbytes == 262_144_000 + 120_000_060 + 262_144 == 382_406_204


def test_least_time_is_memory_bound_on_v5e():
    peak = counts.peaks("TPU v5 lite")
    t, bound = counts.least_time_s(
        *counts.risk_step_work(65536, 1000, 15, 2_000_000), peak)
    assert bound == "memory"
    assert t == pytest.approx(382_406_204 / 819e9)
    t, bound = counts.least_time_s(197e12, 1.0, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")


def test_peak_table_is_keyed_by_device_kind_with_its_source():
    table = json.loads(counts.PEAKS_FILE.read_text())
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1.6e12
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")


def test_internlm2_matmul_params():
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048
    # (12,582,912) and SwiGLU 3 x 2048 x 8192 (50,331,648); 24 layers,
    # plus the 2048 x 92,544 output head
    assert counts.lm_matmul_params(INTERNLM2) == \
        24 * (12_582_912 + 50_331_648) + 189_530_112 == 1_699_479_552


def test_internlm2_decode_and_prefill_flops():
    # decode at a 1024-key context: 2 x 1,699,479,552 plus
    # 4 x 24 layers x 16 heads x 128 x 1024 keys
    assert counts.lm_decode_flops(INTERNLM2, 1024) == \
        3_398_959_104 + 201_326_592
    # prefill of 1024 tokens: 2 x params x 1024, plus causal attention over
    # 1024 x 1025 / 2 = 524,800 query-key pairs at 4 x 24 x 16 x 128 each
    assert counts.lm_prefill_flops(INTERNLM2, 1024) == \
        2 * 1_699_479_552 * 1024 + 196_608 * 524_800
