"""The control of each cell: the reference put in the program's place with
int8 matrix products, the step below the bfloat16 matrix operands the
configurations state, read by the cell's own check after a tiny CPU run,
comes out as not correct."""
import time

import pytest

import bench_tiny


@pytest.mark.parametrize("workload", [bench_tiny.RL_CELL,
                                      bench_tiny.SERVE_CELL])
def test_control_is_not_correct(workload):
    import jax

    from bench import harness, peaks, reference, run

    peaks.PEAKS.setdefault(jax.devices()[0].device_kind,
                           peaks.PEAKS["TPU v5 lite"])
    ctx = bench_tiny.context(workload, seed=5, seconds=2.0)
    m = run.measure(ctx, jax.devices()[:1], harness.CompileClock(),
                    time.perf_counter())
    limits = ctx.cell["limits"]
    program = run.check(ctx, m)
    control = run.check(ctx, m, control=reference.INT8)
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control
