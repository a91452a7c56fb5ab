"""Roofline HLO-parsing units — no multi-device requirement."""
from repro.launch import roofline as roof


# ------------------------------------------------------------ HLO parsing
HLO = """
ENTRY main {
  %p = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p), replica_groups={{0,1}}
  %ag = bf16[64,512]{1,0} all-gather(%p), dimensions={0}
  %rs = f32[32]{0} reduce-scatter(%p), dimensions={0}
  %a2a = f32[16,16]{1,0} all-to-all(%p), dimensions={0}
  %cp = u8[1024]{0} collective-permute(%p)
  %t = (f32[10,10]{1,0}, f32[5]{0}) all-reduce(%x, %y)
  %start = f32[100]{0} all-gather-start(%p)
  %done = f32[100]{0} all-gather-done(%start)
}
"""


def test_collective_bytes_parsing():
    got = roof.collective_bytes(HLO)
    assert got["all-reduce"] == (128 * 256 * 4 + (100 + 5) * 4) * 2.0
    # all-gather counted once for start (done skipped) + plain ag
    assert got["all-gather"] == 64 * 512 * 2 + 100 * 4
    assert got["reduce-scatter"] == 32 * 4
    assert got["all-to-all"] == 16 * 16 * 4
    assert got["collective-permute"] == 1024


def test_shape_bytes_tuple_and_scalar():
    assert roof._shape_bytes("(f32[2,3]{1,0}, bf16[4]{0})") == 24 + 8
    assert roof._shape_bytes("f32[]") == 4  # scalar: empty dims


def test_roofline_terms():
    r = roof.Roofline(flops=197e12, bytes_accessed=819e9, coll_bytes=50e9,
                      coll_breakdown={}, peak_memory=8 << 30)
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 1.0) < 1e-9
    assert abs(r.collective_s - 1.0) < 1e-9
    assert r.step_s == max(r.compute_s, r.memory_s, r.collective_s)
