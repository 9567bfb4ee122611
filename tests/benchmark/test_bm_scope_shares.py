"""``benchmark/scope_shares.py``: a kept trace's device time by named
kernel and by the scope in the ops' ``op_name``."""

import pytest

from benchmark import scope_shares

OPS = [
    ("%flash_bd_fwd.3", "custom-call", "jit(f)/layer_0/attn/flash_bd_fwd",
     6e-3),
    ("%flash_bd_fwd.9", "custom-call", "jit(f)/layer_1/attn/flash_bd_fwd",
     7e-3),
    ("%moe_gmm_dw", "custom-call", "jit(f)/layer_0/moe/experts/moe_gmm_dw",
     1e-3),
    ("%fusion.12", "fusion", "jit(f)/layer_0/moe/experts/take", 2e-3),
    ("%fusion.13", "fusion", "jit(f)/layer_0/moe/combine/scatter-add", 4e-3),
    ("%fusion.14", "fusion", "jit(f)/layer_0/mlp_norm/mul", 5e-4),
    ("%copy.2", "copy", "", 25e-5),
]


def test_a_kernel_counts_once_and_a_scope_takes_what_is_left():
    got = scope_shares.shares(OPS, ["moe/experts", "moe/combine"],
                              ["flash_", "moe_gmm_"])
    assert got["total_s"] == pytest.approx(sum(op[3] for op in OPS))
    assert got["kernels"]["flash_bd_fwd"] == {"s": pytest.approx(13e-3),
                                              "n": 2}
    # the grouped product ran under moe/experts: counted as a kernel
    assert got["kernels"]["moe_gmm_dw"]["n"] == 1
    assert got["scopes"] == {"moe/experts": {"s": 2e-3, "n": 1},
                             "moe/combine": {"s": 4e-3, "n": 1}}
    assert got["rest"] == {"fusion": {"s": 5e-4, "n": 1},
                           "copy": {"s": 25e-5, "n": 1}}
    parts = [e["s"] for g in ("kernels", "scopes", "rest")
             for e in got[g].values()]
    assert sum(parts) == pytest.approx(got["total_s"])


def test_no_scope_and_no_kernel_asked_is_all_rest():
    got = scope_shares.shares(OPS, [], [])
    assert got["kernels"] == {} and got["scopes"] == {}
    assert got["rest"]["custom-call"]["n"] == 3


def test_read_ops_takes_the_scope_from_the_metadata(tmp_path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    for name in ("/device:TPU:0", "/host:CPU"):
        plane = space.planes.add(name=name)
        plane.stat_metadata[1].name = "tf_op"
        plane.stat_metadata[2].name = "jit(f)/moe/route/top_k"
        by_value = plane.event_metadata[1]
        by_value.name = "%fusion.7 = f32[8]{0} fusion(%p), kind=kLoop"
        by_value.stats.add(metadata_id=1, str_value="jit(f)/moe/combine/add")
        by_ref = plane.event_metadata[2]
        by_ref.name = "%sort.1 = s32[8]{0} sort(%q)"
        by_ref.stats.add(metadata_id=1, ref_value=2)
        loop = plane.event_metadata[3]
        loop.name = "%while.2 = (f32[8]) while(%t), body=%b"
        ops = plane.lines.add(name="XLA Ops")
        for mid, ps in ((1, 3_000_000), (2, 1_000_000), (3, 9_000_000)):
            ops.events.add(metadata_id=mid, duration_ps=ps)
        plane.lines.add(name="XLA Modules").events.add(
            metadata_id=1, duration_ps=5)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert scope_shares.read_ops(str(path)) == [
        ("%fusion.7", "fusion", "jit(f)/moe/combine/add",
         pytest.approx(3e-6)),
        ("%sort.1", "sort", "jit(f)/moe/route/top_k", pytest.approx(1e-6))]
    assert scope_shares.main([str(tmp_path)]) == 0
    assert scope_shares.main([str(tmp_path / "none")]) == 1
