"""Required bytes at whisper-tiny width against hand arithmetic; peaks."""
import pytest

from chipbench import work

D = 39_593_856  # whisper-tiny parameters


def test_encode_bytes_whisper_tiny():
    # read 39,593,856 f32 (158,375,424 B), write as many int32
    assert work.encode_bytes(D) == 158_375_424 + 158_375_424 == 316_750_848


def test_flush_and_release_bytes_whisper_tiny():
    assert work.flush_read_bytes(D) == 158_375_424
    assert work.release_bytes(D) == 2 * 158_375_424


def test_step_bytes_papaya_session():
    # one K = 10 session: 10 x (encode + flush read) + one params update
    assert work.step_bytes(D, 10, 1) == 10 * 475_126_272 + 316_750_848
    assert work.step_bytes(D, 10, 1) == 5_068_013_568


def test_encode_time_at_peak():
    least = work.encode_bytes(D) / work.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert least == pytest.approx(3.8675e-4, rel=1e-3)  # 0.387 ms


def test_peaks_v5e_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_bf16"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")
