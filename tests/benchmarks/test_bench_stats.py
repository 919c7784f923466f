"""Percentile, spread and tokens/s arithmetic on hand-made lists, and the
cost functions against hand counts."""
import math

import pytest

from benchmarks import stats
from benchmarks.costs import decoder_train, flash_attention


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (100, 10), (10, 1)])
def test_percentile_nearest_rank(q, want):
    assert stats.percentile(list(range(1, 11)), q) == want


def test_percentile_skips_nan_and_handles_empty():
    assert stats.percentile([float("nan"), 3.0], 95) == 3.0
    assert math.isnan(stats.percentile([], 50))


def test_train_tokens_per_s_over_all_steps_and_all_time():
    assert stats.train_tokens_per_s(100, [0.5, 1.0, 2.0]) == \
        pytest.approx(150.0)


def test_spread_is_interquartile_over_median():
    vals = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    import statistics
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)


def test_flash_cost_against_a_hand_count():
    # B=1, H=2, Hkv=1, S=4, Dh=8 in bf16: one matmul unit is
    # 2*1*2*4*4*8 = 512 operations, halved by the causal mask = 256;
    # forward 2 units, backward 5 -> 1792.  Q is 1*4*2*8*2 = 128 bytes,
    # K and V 64 each: forward reads Q,K,V writes O = 384; backward
    # reads Q,K,V,O,dO writes dQ,dK,dV = 768.
    c = flash_attention.cost(batch=1, seq=4, num_heads=2, num_kv_heads=1,
                             head_dim=8)
    assert c == {"flops": 1792, "bytes": 1152}


def test_train_flops_a_token_against_a_hand_count():
    arch = {"embed_dim": 4, "ff_dim": 8, "num_heads": 2, "head_dim": 2,
            "num_kv_heads": 1, "num_layers": 1, "num_experts": 1,
            "top_k": 1, "vocab_size": 10}
    # attention weights 4*4 + 2*4*2 + 4*4 = 48, MLP 3*4*8 = 96, head 40
    assert decoder_train.matmul_params_per_token(arch) == 184
    # attention at S=6: 2 matmuls * 2 * (6/2) * 4 = 48 forward
    assert decoder_train.flops_per_token(arch, 6) == 3 * (2 * 184 + 48)
    moe = {**arch, "num_experts": 4, "top_k": 2}
    assert decoder_train.matmul_params_per_token(moe) == 48 + 192 + 16 + 40


def test_grouped_matmul_cost_against_a_hand_count():
    from benchmarks.costs import grouped_matmul
    # T=8 tokens, top-2 of 4 experts, capacity int(1.25*8*2/4)=5 slots
    # an expert: 16 assignments < 20 slots -> 16 rows; three matmuls of
    # 2*16*4*8 = 1024 operations each.  Weights 3*4*4*8*2 = 768 bytes,
    # rows 16*(2*4 + 3*8 + 4)*2 = 1152.
    c = grouped_matmul.cost(batch=2, seq=4, embed_dim=4, ff_dim=8,
                            num_experts=4, top_k=2, capacity_factor=1.25)
    assert c == {"flops": 3072, "bytes": 1920}
