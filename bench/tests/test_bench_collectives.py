"""Device time inside collective operations, from hand-made intervals."""

import pytest

from bench import trace_reduce as tr


def _trace():
    # window [0, 120). Device 0: a while over [0, 100) holds a fusion, an
    # async all-reduce (start and done) and a plain all-reduce; an
    # all-gather after the window. Device 1: the same while holding one
    # all-reduce, then an all-to-all and a collective-permute-done inside
    # the window and a reduce-scatter after it.
    ops = {"0": [("while.1", 0, 100), ("fusion.2", 10, 30),
                 ("all-reduce-start.3", 30, 40), ("all-reduce-done.3", 40, 45),
                 ("all-reduce.4", 60, 70), ("copy-start.5", 80, 90),
                 ("all-gather.5", 150, 160)],
           "1": [("while.1", 0, 100), ("all-reduce.4", 50, 80),
                 ("all-to-all.1", 110, 115),
                 ("collective-permute-done.2", 115, 118),
                 ("reduce-scatter.1", 130, 140)]}
    modules = {"0": [("jit_multi(1)", 0, 100)],
               "1": [("jit_multi(1)", 0, 118)]}
    host = [("bench.window", 0, 120)]
    return tr.Trace(ops=ops, modules=modules, host=host)


def test_collective_time_per_device():
    t = _trace()
    # [30, 45) and [60, 70): the nested ones once, the late one left out
    assert tr.collective_time(t, "0", 0, 120) == pytest.approx(25e-9)
    assert tr.collective_time(t, "1", 0, 120) == pytest.approx(38e-9)


def test_reduce_averages_collective_time_over_the_cell_chips():
    assert tr.reduce(_trace(), n_chips=1)["collective_s"] == \
        pytest.approx(25e-9)
    assert tr.reduce(_trace(), n_chips=2)["collective_s"] == \
        pytest.approx((25e-9 + 38e-9) / 2)


# op names as a TPU trace gives them: the HLO text, named by JAX
PSUM = ("%psum.48 = s32[141043,1000]{0,1:T(8,128)} all-reduce(s32[141043,"
        "1000]{0,1:T(8,128)} %bitcast_bitcast_fusion.1), channel_id=1, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_17")
STATS = ("%all-reduce.13 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce("
         "f32[]{:T(128)} %get-tuple-element.404, f32[]{:T(128)} "
         "%get-tuple-element.403), channel_id=1, to_apply=%region_19.21")
FUSION = ("%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128]{1,0:T(8,128)}"
          " %all-reduce.2), kind=kLoop, calls=%fused_computation.3")
WHILE = ("%while.50 = (s32[]{:T(128)}, pred[2817,8192]{1,0:T(8,128)(4,1)}) "
         "while((s32[]{:T(128)}, pred[2817,8192]{1,0:T(8,128)(4,1)}) "
         "%tuple.131), condition=%region_12, body=%region_5")


@pytest.mark.parametrize("name, collective", [
    ("all-reduce.12", True), ("%all-reduce-start.3", True),
    ("all-gather-done", True), ("reduce-scatter.7", True),
    ("collective-permute-start.1", True), ("all-to-all", True),
    ("fusion.3", False), ("all-reducer", False), ("copy-start.2", False),
    ("reduce.5", False), (PSUM, True), (STATS, True), (FUSION, False),
    (WHILE, False),
    ("%ag = f32[8,4]{1,0} all-gather-start(f32[2,4]{1,0} %p), dims={0}",
     True)])
def test_collective_names(name, collective):
    assert tr.is_collective(name) == collective


def test_collective_named_by_jax_is_read_by_its_opcode():
    # the W-delta all-reduce as the chip's trace names it, nested in a while
    t = tr.Trace(ops={"0": [(WHILE, 0, 100), (PSUM, 20, 30),
                            (FUSION, 30, 60), (STATS, 70, 72)]},
                 modules={}, host=[("bench.window", 0, 100)])
    assert tr.reduce(t)["collective_s"] == pytest.approx(12e-9)


def test_one_chip_trace_has_no_collective_time():
    t = tr.Trace(ops={"0": [("fusion.1", 0, 50)]}, modules={},
                 host=[("bench.window", 0, 100)])
    assert tr.reduce(t)["collective_s"] == 0.0
