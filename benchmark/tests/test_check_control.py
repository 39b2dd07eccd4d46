"""The control of the correctness check, at a size a test run can hold: the
reference computed with fp8 matmuls, put in the program's place, comes out
NOT correct, on three seeds, while the program itself comes out correct
(test_drive.py)."""

import pytest

from conftest import drive_tiny


@pytest.mark.parametrize("seed", [1, 2**31 + 11, 77])
def test_fp8_reference_fails_the_training_check(overlay, seed):
    out, r = drive_tiny("tiny-train", seed, 0.3, with_control=True)
    assert out["correct"] is True, r.compared
    assert r.control_correct is False, r.control_compared


@pytest.mark.parametrize("seed", [1, 2**31 + 11, 77])
def test_fp8_reference_fails_the_serving_check(overlay, seed):
    out, r = drive_tiny("tiny-chat", seed, 1.0, with_control=True)
    assert out["correct"] is True, r.compared
    assert r.control_correct is False, r.control_compared
