"""Operations and bytes of the Q-net and the fused TD update, from
shapes."""
import benchtest  # noqa: F401
import pytest

from benchlib import flops


def test_qnet_forward_flops_from_shapes():
    # 58 -> 256 -> 64 -> 11: matmuls 2*(58*256 + 256*64 + 64*11) = 63,872,
    # bias adds 256 + 64 + 11, ReLUs 256 + 64
    assert flops.qnet_forward_flops(58, (256, 64), 11) == 63872 + 331 + 320
    assert flops.mlp_param_count([58, 256, 64, 11]) == (
        58 * 256 + 256 + 256 * 64 + 64 + 64 * 11 + 11)


def test_td_update_cost_from_shapes():
    c = flops.td_update_cost(64, 58, (256, 64), 11)
    fwd = flops.qnet_forward_flops(58, (256, 64), 11)
    mm = [2 * 58 * 256, 2 * 256 * 64, 2 * 64 * 11]
    params = flops.mlp_param_count([58, 256, 64, 11])
    assert c["flops"] == 64 * (3 * fwd + 2 * sum(mm) - mm[0]) + 12 * params
    assert c["bytes"] == 64 * (2 * 58 * 4 + 12) + 7 * 4 * params
    # twice the batch, about twice the work
    c2 = flops.td_update_cost(128, 58, (256, 64), 11)
    assert 1.9 < c2["flops"] / c["flops"] < 2.0


def test_roofline_share_takes_the_larger_bound():
    share, bound = flops.roofline_share(2e9, 1e6, 1e-3, 1e12, 1e9)
    assert bound == "compute" and share == pytest.approx(200.0)
    share, bound = flops.roofline_share(1e3, 8e6, 1e-2, 1e12, 1e9)
    assert bound == "memory" and share == pytest.approx(80.0)
    assert flops.roofline_share(1, 1, 0.0, 1, 1) is None
