"""The kernel cost model against the program's own byte model."""

import pytest

from harness import kernels
from repro.kernels.ivf_scan import stage0_bytes_model

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.mark.parametrize("n_probe,max_len,d0,member", [
    (12, 256, 128, 1), (8, 128, 64, 4), (1, 512, 256, 1)])
def test_bytes_are_the_fused_model_plus_the_query(n_probe, max_len, d0,
                                                  member):
    cost = kernels.ivf_scan_cost(queries=3, n_probe=n_probe, max_len=max_len,
                                 d0=d0, k=64, member_bytes=member)
    fused = stage0_bytes_model(n_lists=4096, max_len=max_len,
                               n_probe=n_probe, d0=d0, k=64,
                               member_bytes=member)["fused_bytes"]
    assert cost["bytes"] == pytest.approx(3 * (fused + 4 * d0))
    assert cost["flops"] == pytest.approx(3 * 2 * n_probe * max_len * d0)


def test_the_ivf_scan_is_memory_bound_on_a_v5e():
    cost = kernels.ivf_scan_cost(queries=1, n_probe=12, max_len=256, d0=128,
                                 k=64, member_bytes=1)
    seconds, bound = kernels.least_seconds(cost, PEAKS)
    assert bound == "memory"
    assert seconds == pytest.approx(cost["bytes"] / 819e9)
