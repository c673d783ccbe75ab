"""The least bytes of one ``_group_kernel`` dispatch, counted by hand."""
import kernel_bytes
import pytest


def test_hand_count():
    # two groups: 1000 and 10 stage rows, 256 and 1 scenarios
    rows = 4 * (1000 + 10)            # 4 composition columns per row
    scen = 3 * (256 + 1)              # PUE, CI in; operational carbon out
    group = 24 * 2                    # 12 + 5 + 1 + 1 in, 5 out
    assert kernel_bytes.live_bytes([1000, 10], [256, 1]) \
        == 4 * (rows + scen + group)


def test_phi2_qps_sweep_order_of_magnitude():
    # nine groups of ~30k rows: ~4.3 MB, ~5 us at 819 GB/s
    b = kernel_bytes.live_bytes([30000] * 9, [1] * 9)
    assert b == 4 * (9 * (4 * 30000 + 3 + 24))
    assert b / 819e9 == pytest.approx(5.3e-6, rel=0.01)


def test_mismatched_lengths_refused():
    with pytest.raises(ValueError):
        kernel_bytes.live_bytes([1, 2], [1])
