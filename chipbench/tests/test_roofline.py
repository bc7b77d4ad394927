"""Operation and byte counts against hand-computed ones."""
from __future__ import annotations

import json

import pytest

from chipbench.arch import dense
from chipbench.harness import roofline as R
from chipbench.harness import spec

QWEN = json.loads((spec.BENCH_DIR / "configs" / "qwen2-7b-l20.json")
                  .read_text())["arch"]
GRANITE = json.loads((spec.BENCH_DIR / "configs" / "granite-3-2b.json")
                     .read_text())["arch"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layer_params_by_hand():
    # qwen2: q 3584x3584, k and v 3584x512, o 3584x3584, MLP 3x3584x18944
    assert dense.layer_matmul_params(QWEN) == (
        3584 * 3584 + 2 * 3584 * 512 + 3584 * 3584 + 3 * 3584 * 18944)
    # granite: q 2048x2048, k and v 2048x512, o, MLP 3x2048x8192
    assert dense.layer_matmul_params(GRANITE) == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192)


def test_paged_decode_counts_by_hand():
    # granite: Hk 8, G 4, d 64.  Two slots attending 3000 and 1000 keys:
    # flops 2 (QK, PV) x 2 x 32 heads x 64 x 4000 keys
    f, b = dense.paged_decode(GRANITE, [3000, 1000])
    assert f == 4 * 32 * 64 * 4000
    # bytes: K and V, 8 heads x 64 x 2 B, per key; q in, o out per slot
    assert b == 2 * 8 * 64 * 2 * 4000 + 2 * 2 * 32 * 64 * 2
    t, bound = R.least_time(f, b, V5E)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_paged_prefill_counts_by_hand():
    # qwen2: Hk 4, G 7, d 128; rows 512..767 of one prompt: row i attends
    # 512 + i + 1 keys, 256*512 + 256*257/2 key pairs in all
    f, b = dense.paged_prefill(QWEN, 512, 256)
    assert f == 4 * 28 * 128 * (256 * 512 + 256 * 257 // 2)
    assert b == 2 * 4 * 128 * 2 * 768 + 2 * 256 * 28 * 128 * 2
    assert R.least_time(f, b, V5E)[1] == "compute"


def test_decode_token_step_reads_every_weight_once():
    f, b = dense.decode_token_step(QWEN, [1000] * 16)
    P = dense.layer_matmul_params(QWEN)
    assert f == pytest.approx(20 * (2 * P * 16 + 4 * 28 * 128 * 16000)
                              + 2 * 3584 * 152064 * 16)
    kv = 20 * 2 * 4 * 128 * 2 * 16000
    assert b == pytest.approx(20 * P * 2 + 3584 * 152064 * 2 + kv
                              + 20 * (2 * 16 * 28 * 128 * 2)
                              + 20 * 2 * 4 * 128 * 2 * 16 + 16 * 3584 * 2)
    # weights 9.32 GB + head 1.09 GB + KV 0.66 GB: about 13.5 ms on a v5e
    assert R.least_time(f, b, V5E)[0] == pytest.approx(0.0135, rel=0.01)


def test_prefill_chunk_head_only_on_last():
    f0, b0 = dense.prefill_chunk(GRANITE, 0, 256, last=False)
    f1, b1 = dense.prefill_chunk(GRANITE, 0, 256, last=True)
    assert f1 - f0 == 2 * 2048 * 49155
    assert b1 - b0 == 2048 * 49155 * 2


def test_decode_block_attrition():
    # slot 1 at 99 cached with 8 to go, slot 2 at 9 with 3 to go
    ctx = R.decode_block_contexts(((1, 99, 8), (2, 9, 3)), 8)
    assert ctx[0] == [100, 10] and ctx[2] == [102, 12] and ctx[3] == [103]
    assert ctx[7] == [107]
