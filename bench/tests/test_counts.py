"""FLOP and byte counts against hand-worked yi-6b shapes."""

import pytest

import cells
import peaks

METRIC = cells.load_module("metrics", "pod_gemm_roofline")


def test_peaks_by_device_kind():
    assert peaks.peak("TPU v5 lite") == {"bf16_flops": 197e12,
                                         "hbm_bytes_s": 819e9}
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_yi6b_counts_by_hand():
    conf = cells.load_json("configs", "yi-6b")
    ref = cells.load_module("refs", conf["ref"])
    # per layer: q 4096*32*128 + k,v 2*4096*4*128 + o 32*128*4096
    # + gate, up, down 3*4096*11008 = 173,015,040; 32 layers
    assert ref.body_params(conf) == 5_536_481_280
    # with both 64000 x 4096 tables and the 65 norms: the 6,061,035,520
    # parameters (12,122,071,040 bf16 bytes) of the served model
    assert ref.body_params(conf) + 2 * 64000 * 4096 + 65 * 4096 \
        == 6_061_035_520
    # attention: 32 layers x 4 x 32 heads x 128 per position attended
    assert ref.token_flops(conf, 1) == 11_072_962_560 + 524_288
    assert ref.token_flops(conf, 1000) == 11_072_962_560 + 524_288_000
    assert ref.head_flops(conf) == 524_288_000
    assert (4096, 11008) in ref.gemm_shapes(conf)
    assert (4096, 512) in ref.gemm_shapes(conf)


def test_gemm_shape_mapping_and_roofline_bound():
    cands = [(4096, 4096), (4096, 512), (4096, 11008), (11008, 4096),
             (4096, 64000)]
    # a decode up-projection padded to 11264 columns maps back to 11008
    assert METRIC.model_shape(((8, 4096), (4096, 11264), False), cands,
                              [8, 4096]) == (8, 4096, 11008)
    # a prefill down-projection padded to 11264 rows of K
    assert METRIC.model_shape(((4096, 11264), (11264, 4096), False), cands,
                              [8, 4096]) == (4096, 11008, 4096)
    # decode [8,4096] x [4096,11008] in bf16 is bound by bytes
    m, k, n = 8, 4096, 11008
    t_flops = 2 * m * n * k / 197e12
    t_bytes = (m * k + k * n + m * n) * 2 / 819e9
    assert t_flops == pytest.approx(3.662e-6, rel=1e-3)
    assert t_bytes == pytest.approx(1.104e-4, rel=1e-3)
