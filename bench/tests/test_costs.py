"""Each kernel's and model's cost function against hand arithmetic at the
cells' shapes."""
import json

import pytest

from bench.core import BENCH
from bench.costs import (model_dit, model_lm, sla2_decode_paged,
                         sla2_prefill_paged, sla2_sparse_fwd)

DIT = json.load(open(BENCH / "configs" / "wan_dit_1_3b.json"))
LM = json.load(open(BENCH / "configs" / "internlm2_20b.json"))


def test_sparse_fwd_row_at_wan_shape():
    # 32,768 queries keep round(0.05 * 512) = 26 blocks of 64 keys, d = 128
    ops, nbytes = sla2_sparse_fwd.per_row(32768, 128, 64, 26)
    assert ops == 4 * 32768 * 26 * 64 * 128 == 27_917_287_424
    assert nbytes == 4 * 32768 * 128 * 2 + 4 * 32768 == 33_685_504


def test_sparse_fwd_is_bound_by_int8_ops():
    peaks = {"ops_int8": 394e12, "hbm_bw": 819e9}
    ops, nbytes = sla2_sparse_fwd.per_row(32768, 128, 64, 26)
    assert sla2_sparse_fwd.ideal_s(ops, nbytes, peaks) == ops / 394e12


@pytest.mark.parametrize("t,n_sel", [(1, 1), (64, 1), (65, 2), (256, 4),
                                     (5000, 4)])
def test_decode_row_pages_kept(t, n_sel):
    ops, nbytes = sla2_decode_paged.per_row(t, heads=48, kv_heads=8, d=128,
                                            block_k=64, k_sel=4)
    n_tok = min(t, n_sel * 64)
    assert nbytes == (2 * n_sel * 64 * 8 * 128 * 2 + 8 * (128 * 128 + 128) * 4
                      + 2 * 48 * 128 * 2)
    assert ops == 8 * 48 * 128 * n_tok + 2 * 48 * (128 * 128 + 128)


def test_prefill_chunk_at_offset():
    # 64 queries at offset 8192 see 8192 + 1 .. 8192 + 64 keys
    ops, nbytes = sla2_prefill_paged.per_call(8192, 64, heads=48,
                                              kv_heads=8, d=128)
    keys = sum(8192 + i + 1 for i in range(64))
    assert ops == 4 * 48 * 128 * keys
    assert nbytes == 2 * (8192 + 64) * 8 * 128 * 2 + 2 * 64 * 48 * 128 * 2


def test_dit_request_step_flops():
    d, hd, ff, n, m = 1536, 12 * 128, 8960, 32768, 512
    per_tok = (8 * d * hd + 4 * d * hd + 4 * d * ff + 4 * hd * 26 * 64
               + 4 * 12 * 128 * 128 + 4 * hd * m)
    want = 30 * (n * per_tok + 4 * m * d * hd) + n * 4 * d * 16
    assert model_dit.flops_per_request_step(DIT) == want
    assert 2.8e9 < want / n < 3.1e9          # ~2.9 GFLOP per latent token


def test_lm_step_flops():
    d, ff, v = 6144, 16384, 92544
    dense = 2 * d * (48 + 16) * 128 + 2 * 48 * 128 * d + 6 * d * ff
    head = 2 * d * v
    got = model_lm.step_flops(LM, (0, 64), [300], k_sel=4)
    prefill = 6 * (64 * dense + 4 * 48 * 128 * (64 * 65 / 2)) + head
    decode = 6 * (dense + 4 * 48 * 128 * 256 + 2 * 48 * (128 * 128 + 128)) \
        + head
    assert got == pytest.approx(prefill + decode, rel=1e-12)
    assert 5.7e9 < dense * 6 + head < 5.9e9   # ~5.8 GFLOP per decoded token
