"""The benchmark's FLOP counts against counts made by hand."""
import json
import os

import pytest

from bench_fixtures import BENCH, import_harness

import_harness()
import spec  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


ARCH = spec._load_module(os.path.join(BENCH, "arch", "dense_gqa.py"),
                         "bench_arch_dense_gqa_test")

# qwen2-0.5b, per layer: q 896*14*64 = 802,816; k and v 2*896*2*64 = 229,376;
# o 802,816; SwiGLU 3*896*4864 = 13,074,432; sum 14,909,440.  24 layers =
# 357,826,560; tied head 896*151,936 = 136,134,656.  Attention at 4096:
# 12 * 24 * 14 * 64 * 2048.5 (mean causal context) = 528,611,328.
QWEN_S4096 = 6 * (357_826_560 + 136_134_656) + 528_611_328
# h2o-danube-1.8b, 12 layers, per layer: q 2560*32*80 = 6,553,600; k and v
# 2*2560*8*80 = 3,276,800; o 6,553,600; SwiGLU 3*2560*6912 = 53,084,160;
# sum 69,468,160.  Head 2560*32,000 = 81,920,000.  Attention at 4096 (the
# window does not cap it): 12 * 12 * 32 * 80 * 2048.5 = 755,159,040.
DANUBE_S4096 = 6 * (12 * 69_468_160 + 81_920_000) + 755_159_040


@pytest.mark.parametrize("name,seq,want", [
    ("qwen2-0.5b", 4096, QWEN_S4096),
    ("qwen2-0.5b", 512, 6 * (357_826_560 + 136_134_656)
     + 12 * 24 * 14 * 64 * 256.5),
    ("h2o-danube-1.8b", 4096, DANUBE_S4096),
])
def test_model_flops_per_token_matches_hand_count(name, seq, want):
    assert ARCH.model_flops_per_token(_config(name), seq) == pytest.approx(
        want, rel=1e-12)


def test_window_caps_mean_context():
    # 8192 queries, window 4096: the first 4096 see 1..4096 keys, the rest
    # 4096 each: (4096 * 4097 / 2 + 4096 * 4096) / 8192
    assert ARCH.mean_context(8192, 4096) == 3072.25
    assert ARCH.mean_context(4096, 4096) == 2048.5
    assert ARCH.mean_context(512, None) == 256.5


def test_executed_matmul_flops_counts_remat_and_full_attention():
    c = _config("qwen2-0.5b")
    t = 16 * 512
    layers = 2 * t * 24 * 14_909_440 + 4 * t * 512 * 24 * 14 * 64
    head = 2 * t * 896 * 152_064          # the padded vocabulary
    got = ARCH.executed_matmul_flops(c, 16, 512, remat=True,
                                     padded_vocab=152_064)
    assert got == 4 * layers + 3 * head
    assert ARCH.executed_matmul_flops(c, 16, 512, remat=False,
                                      padded_vocab=152_064) == (
        3 * layers + 3 * head)
