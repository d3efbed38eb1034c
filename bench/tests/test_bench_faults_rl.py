"""The RL cell's check, driven through a whole tiny run on the CPU (the
chip check skipped): a sound run is correct, and each fault the cell can
have, planted underneath the timed path, makes ``correct`` false."""
import pytest

import bench_tiny


def test_sound_run_is_correct():
    result, checks = bench_tiny.run_tiny(bench_tiny.RL_CELL)
    assert result["correct"], checks
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_is_not_correct(fault):
    from bench import faults

    with faults.planted("rl", fault):
        result, checks = bench_tiny.run_tiny(bench_tiny.RL_CELL)
    assert not result["correct"], checks
