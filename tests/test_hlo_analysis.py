"""Unit tests for the roofline-term extraction (dist/hlo_analysis)."""

import pytest

from repro.dist.hlo_analysis import (
    PEAKS,
    Roofline,
    collective_stats,
    peaks_for,
)

HLO = """
  %all-gather.6 = f32[128,512]{0,1} all-gather(%copy), channel_id=1, replica_groups=[2,2]<=[4], dimensions={1}
  %dot = f32[128,256]{1,0} dot(%param, %all-gather.6)
  %all-reduce.1 = bf16[16,1024]{1,0} all-reduce(%x), replica_groups=[4,4]<=[16], to_apply=%add
  %reduce-scatter.2 = f32[64,64]{1,0} reduce-scatter(%y), replica_groups=[1,8]<=[8], dimensions={0}
  %collective-permute.3 = f32[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %tuple.14 = (s32[], f32[128,256]{1,0}) tuple(%c, %all-gather.6)
  %all-gather-start.1 = (bf16[4,128]{1,0}, bf16[8,128]{1,0}) all-gather-start(%w), replica_groups=[2,2]<=[4], dimensions={0}
  %all-gather-done.1 = bf16[8,128]{1,0} all-gather-done(%all-gather-start.1)
"""


def test_collective_ops_counted_once_and_tuples_ignored():
    st = collective_stats(HLO)
    # 5 real collectives: AG, AR, RS, permute, AG-start (done skipped;
    # the tuple line referencing %all-gather.6 must not match)
    assert st.count == 5
    assert set(st.by_op) == {
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    }


def test_wire_byte_conventions():
    st = collective_stats(HLO)
    ag = 128 * 512 * 4 // 2            # result * (g-1)/g, g=2
    ag_start = 8 * 128 * 2 // 2        # last tuple element, g=2
    ar = 16 * 1024 * 2 * 2 * 3 // 4    # result * 2(g-1)/g, g=4
    rs = 64 * 64 * 4 * 7               # result * (g-1), g=8
    cp = 8 * 8 * 4
    assert st.by_op["all-gather"] == ag + ag_start
    assert st.by_op["all-reduce"] == ar
    assert st.by_op["reduce-scatter"] == rs
    assert st.by_op["collective-permute"] == cp
    assert st.total_bytes == sum(st.by_op.values())


def test_roofline_terms_and_dominance():
    v5e = peaks_for("TPU v5 lite")
    r = Roofline(
        flops=v5e.flops,         # 1 s compute
        hbm_bytes=v5e.hbm_bw * 2,  # 2 s memory
        coll_bytes=v5e.ici_bw / 2,  # 0.5 s collective
        peaks=v5e,
        model_flops=v5e.flops / 2,
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.bound_s == pytest.approx(2.0)
    assert r.useful_flops_frac == pytest.approx(0.5)
    assert r.roofline_frac == pytest.approx(0.25)


def test_schedule_order_preserved():
    st = collective_stats(HLO)
    assert [op for op, _ in st.schedule] == [
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
        "all-gather",
    ]


def test_peaks_keyed_by_device_kind():
    # TPU v5e, as JAX names it: 197 TFLOP/s bf16, 819 GB/s HBM
    v5e = PEAKS["TPU v5 lite"]
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    # a chip the table does not know is an error, never a default
    with pytest.raises(KeyError, match="cpu"):
        peaks_for("cpu")
