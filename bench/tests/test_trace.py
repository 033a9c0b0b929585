"""The trace reduction on a trace recorded on a v5e chip.

``data/index_build_v5e.xplane.pb.gz`` is a ``--trace 1`` run of
``zeshel-yugioh.bert-base.index-build`` (15 s window, 7 bulk_score calls
of 2,000 pairs), gzipped.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

import xplane
from run import kernel_cost

TRACE = Path(__file__).with_name("data") / "index_build_v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary():
    return xplane.reduce(*xplane.load(str(TRACE)))


def test_base_name_of_hlo_event_text():
    assert xplane.base_name("%flash_attention.19 = bf16[3840,256,64] custom-call(...)") \
        == "flash_attention"
    assert xplane.base_name("%fusion = f32[2] fusion(...)") == "fusion"


def test_window_busy_and_idle(summary):
    # the window is the harness's bench.window host span, on the device clock
    assert summary["window_s"] == pytest.approx(15.151912, abs=1e-5)
    assert 0 < summary["busy_s"] <= summary["window_s"]
    assert summary["busy_s"] == pytest.approx(15.134795, abs=1e-5)
    gaps = dict(summary["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(summary["window_s"] - summary["busy_s"], rel=1e-6)
    assert max(gaps, key=gaps.get) == "bench.bulk_score"


def test_ops_exclude_loops_and_rank_the_kernel_first(summary):
    ops = dict(summary["op_seconds"])
    assert "while" not in ops
    assert summary["device_ops"][0][0] == "flash_attention"
    assert len(summary["device_ops"]) <= 10 and len(summary["idle_gaps"]) <= 10
    assert xplane.kernel_seconds(summary, "flash_attention") == pytest.approx(9.255023, abs=1e-5)
    assert sum(ops.values()) <= summary["busy_s"] * 1.0001


def test_flash_roofline_reader(summary):
    from run import metric_reader

    engine = dict(seq_len=256, heads=12, head_dim=64, v_head_dim=64, causal=False,
                  layers=12)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = SimpleNamespace(trace=summary, trace_pairs=14000, engine=engine,
                          peaks=peaks, kernel_cost=kernel_cost)
    share = metric_reader("flash_roofline.build")(ctx)
    flops, nbytes = kernel_cost("flash_attention").cost(14000, 256, 12, 64)
    want = 100 * max(12 * flops / 197e12, 12 * nbytes / 819e9) / 9.255023
    assert share == pytest.approx(want, rel=1e-5)
    assert 0 < share < 100
    ctx.trace = None
    assert metric_reader("flash_roofline.build")(ctx) is None
