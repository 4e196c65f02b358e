"""Required operations and bytes of the BCPNN kernels, against hand
counts, and the peak table."""
import json

import pytest

from bench import work

M1 = (784 * 2, 32 * 128)     # Table-1 Model 1: Ni, Nj
M3 = (4096 * 2, 32 * 128)    # Table-1 Model 3


@pytest.mark.parametrize("shape,flops,nbytes", [
    # 2*B*Ni*Nj; read + write the f32 trace (8*Ni*Nj) + x and y rows
    (M1, 2 * 128 * 1568 * 4096, 8 * 1568 * 4096 + 4 * 128 * (1568 + 4096)),
    (M3, 2 * 128 * 8192 * 4096, 8 * 8192 * 4096 + 4 * 128 * (8192 + 4096)),
])
def test_learn_step_is_bcpnn_update_by_hand(shape, flops, nbytes):
    w = work.learn(*shape, 128)
    assert (w.flops, w.bytes, w.int8_ops) == (flops, nbytes, 0)


@pytest.mark.parametrize("shape,dtype,flops,int8,nbytes", [
    (M1, "fp32", 2 * 64 * 1568 * 4096, 0,
     4 * 1568 * 4096 + 4 * 64 * (1568 + 4096)),
    (M3, "fp32", 2 * 64 * 8192 * 4096, 0,
     4 * 8192 * 4096 + 4 * 64 * (8192 + 4096)),
    (M1, "bf16", 2 * 64 * 1568 * 4096, 0,
     2 * 1568 * 4096 + 4 * 64 * (1568 + 4096)),
    (M3, "int8", 0, 2 * 64 * 8192 * 4096,
     8192 * 4096 + 4 * 64 * (8192 + 4096)),
])
def test_forward_is_bcpnn_fwd_by_hand(shape, dtype, flops, int8, nbytes):
    w = work.forward(*shape, 64, dtype)
    assert (w.flops, w.int8_ops, w.bytes) == (flops, int8, nbytes)


def test_served_group_reads_weights_once_per_group():
    one = work.served(1568, 4096, 10, groups=1, images=64)
    assert one.flops == 64 * (2 * 1568 * 4096 + 2 * 4096 * 10)
    assert one.bytes == (4 * 1568 * 4096 + 4 * 4096 * 10
                         + 64 * 4 * (1568 + 4096 + 4096 + 10))
    two = work.served(1568, 4096, 10, groups=2, images=64)
    assert two.bytes - one.bytes == 4 * 1568 * 4096 + 4 * 4096 * 10
    assert two.flops == one.flops


def test_train_step_counts():
    ni, nj = M3
    unsup = work.unsup_step(ni, nj, 128)
    assert unsup.flops == 4 * 128 * ni * nj
    assert unsup.bytes == work.learn(ni, nj, 128).bytes
    per_image = work.model_flops_train(ni, nj, 2)
    assert per_image == (4 * ni * nj, 2 * ni * nj + 2 * nj * 2)


def test_roofline_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    w = work.learn(*M3, 128)
    assert work.roofline_s(w, peak) == pytest.approx(w.bytes / 819e9)
    big = work.Work(flops=197e12)
    assert work.roofline_s(big, peak) == pytest.approx(1.0)
    assert work.roofline_s(big, peak, chips=4) == pytest.approx(0.25)


def test_peaks_of_the_v5e():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12
    assert peak["int8_ops"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["ici_bytes_per_s"] == 1600e9 / 8
    with open(work.PEAKS_FILE) as f:
        assert "TPU v5e" in json.load(f)["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")
