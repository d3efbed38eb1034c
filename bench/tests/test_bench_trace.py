"""``bench/trace.py`` reduces a small profiler trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``data/record_small_trace.py``: three
rounds of a matrix program and the program's fused paged attention kernel
under the host span ``bench.compute``, each followed by a 20 ms sleep
under ``bench.host``) to fixed numbers."""
import os

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on the path)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")
KERNEL = "fused_paged_attention_pallas"


@pytest.fixture(scope="module")
def reduced():
    from bench import trace

    ops, spans = trace.load(DATA)
    return ops, spans, trace.reduce(ops, spans, (KERNEL,))


def test_host_spans_are_read(reduced):
    _, spans, _ = reduced
    assert [name for name, _, _ in spans] == ["compute", "host"] * 3


def test_busy_and_kernel_time(reduced):
    ops, _, r = reduced
    assert list(ops) == [0] and len(ops[0]) == 18
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(946872e-9, abs=1e-12)
    assert r["kernel_s"][KERNEL] == pytest.approx(356657e-9, abs=1e-12)
    assert r["kernel_calls"][KERNEL] == 3
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fused_paged_attention_pallas.1"
    assert "convolution_tanh_fusion" in names


def test_idle_gaps_are_labelled_by_the_host_span(reduced):
    _, _, r = reduced
    (a, ta), (b, tb) = r["idle_gaps"][:2]
    assert (a, b) == ("host", "host")
    assert ta == pytest.approx(0.021751823, abs=1e-9)
    assert tb == pytest.approx(0.021329963, abs=1e-9)
    assert all(t < 1e-3 for _, t in r["idle_gaps"][2:])
