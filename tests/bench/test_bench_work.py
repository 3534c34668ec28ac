"""Work counts and peaks: hand-worked values at Qwen1.5-4B's shapes."""
import pytest

import bench_smoke  # noqa: F401  (puts bench/ on the path)
import work
from work import flash_decode, model, vq_amm

D, F, L, V = 2560, 6912, 40, 151936


def test_vq_amm_decode_call_at_model_shape():
    ops, nbytes = vq_amm.work(8, D, D, 8, 16)
    assert ops == 2 * 8 * 2560 * 16 + 8 * 320 * 2560 == 7_208_960
    # int8 table + f32 scale + bf16 centroids + bf16 x + f32 out
    assert nbytes == 13_107_200 + 10_240 + 81_920 + 40_960 + 81_920
    t, bound = work.least_time(ops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(13_322_240 / 819e9)


def test_vq_amm_tables_of_a_whole_decode_step():
    shapes = [(D, D)] * 4 + [(D, F)] * 2 + [(F, D)]
    tables = sum(vq_amm.work(8, k, n, 8, 16)[1]
                 - (4 * n + k * 16 * 2 + 8 * k * 2 + 8 * n * 4)
                 for k, n in shapes)
    assert tables == 158_597_120              # 2 * K * N int8 bytes
    assert tables * L == 6_343_884_800        # ~6.34 GB read per step


def test_flash_decode_charges_live_positions_only():
    ops, nbytes = flash_decode.work([100, 200], 20, 20, 128)
    assert ops == 4 * 300 * 20 * 128 == 3_072_000
    assert nbytes == 2 * 300 * 20 * 128 * 2 + 2 * 2 * 20 * 128 * 2
    assert flash_decode.work([], 20, 20, 128) == (0, 0)


@pytest.mark.parametrize("context", [0, 1, 1000])
def test_model_flops_are_twice_the_parameters_plus_attention(context):
    from repro.configs import get_config
    params = get_config("qwen1.5-4b").param_count()   # tied head, no bias
    assert params == 3_560_898_560
    got = model.flops_per_token(layers=L, d=D, heads=20, kv_heads=20,
                                head_dim=128, ff=F, vocab=V,
                                context=context)
    assert got == 2 * params + 4 * L * D * context


def test_peaks_are_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12
    for kind in ("TPU v4", "cpu", "source"):
        with pytest.raises(KeyError):
            work.peaks(kind)
