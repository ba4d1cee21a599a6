"""The reduction from device intervals to the per-layer trace metrics, on a
synthetic two-device trace whose answers are worked out by hand."""

import pytest

from bench import devtrace


def test_union_subtract_length():
    assert devtrace.union([(3, 5), (0, 1), (0.5, 2), (5, 6)]) == [(0, 2), (3, 6)]
    assert devtrace.length([(0, 2), (3, 6)]) == 5
    a = devtrace.union([(0, 10)])
    b = devtrace.union([(1, 2), (4, 6), (9, 12)])
    assert devtrace.subtract(a, b) == [(0, 1), (2, 4), (6, 9)]
    assert devtrace.subtract(b, a) == [(10, 12)]


@pytest.mark.parametrize("name, coll", [
    ("all-reduce.7", True), ("all-reduce-start.2", True), ("all-reduce-done", True),
    ("collective-permute-done.13", True), ("collective-permute.1", True),
    ("all-gather-start.4", True), ("fusion.12", False), ("copy.3", False),
    ("reduce.5", False), ("all-reducer-fusion", False), ("sendfusion.1", False),
    ("%psum.40 = f32[2]{0:T(128)S(1)} all-reduce(f32[2]{0} %pad_maximum_fusion.2), "
     "channel_id=1", True),
    ("%collective-permute-start.8 = (bf16[1,304,1536]{2,1,0}) collective-permute-start("
     "bf16[1,304,1536]{2,1,0} %broadcast.416)", True),
    ("%fusion.124 = (f32[1]{0}, f32[1]{0}) fusion(f32[2]{0} %psum.40), kind=kLoop", False)])
def test_classification_by_hlo_name(name, coll):
    assert devtrace.is_collective(name) is coll


def test_op_name_from_tpu_event():
    ev = ("%fusion.45 = (f32[]{:T(128)}, bf16[608,608,608]{2,1,0:T(8,128)(2,1)}) "
          "fusion(bf16[608,608,608]{2,1,0:T(8,128)(2,1)} %get-tuple-element.322), kind=kLoop")
    assert devtrace.op_name(ev) == "fusion.45"
    assert devtrace.op_name("%all-reduce.3 = f32[] all-reduce(f32[] %x)") == "all-reduce.3"
    assert devtrace.op_name("copy.2") == "copy.2"


def test_leaves_drop_containers():
    evs = [("while.1", 0, 10), ("fusion.1", 1, 3), ("fusion.2", 4, 9), ("copy", 12, 13)]
    assert [e[0] for e in devtrace.leaves(evs)] == ["fusion.1", "fusion.2", "copy"]


def trace():
    # two devices; host spans from 0 to 20 s
    host = [("bench.dispatch", 0.0, 1.0), ("bench.wait", 1.0, 19.0), ("bench.read", 19.0, 20.0)]
    dev0 = [("fusion.1", 2.0, 6.0), ("all-reduce.1", 5.0, 8.0),   # 1 s hidden, 2 exposed
            ("fusion.2", 8.0, 12.0), ("collective-permute-done.1", 13.0, 14.0)]
    dev1 = [("fusion.1", 2.0, 7.0), ("all-reduce.1", 6.0, 8.0),   # 1 s hidden, 1 exposed
            ("fusion.2", 8.0, 16.0)]
    return devtrace.Trace(ops={"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host=host)


def test_reduce_by_hand():
    r = devtrace.reduce(trace())
    assert r.window_s == 20.0
    # busy: dev0 [2,12]+[13,14] = 11; dev1 [2,16] = 14
    assert r.busy_s == pytest.approx(12.5)
    # idle share 1 - 12.5 / 20
    assert 100 * (1 - r.busy_s / r.window_s) == pytest.approx(37.5)
    # non-collective: dev0 [2,6]+[8,12] = 8; dev1 [2,7]+[8,16] = 13
    assert r.compute_busy_s == pytest.approx(10.5)
    # collectives, on the device with most: dev0 3 + 1 = 4 (dev1 2)
    assert r.collective_s == pytest.approx(4.0)
    # exposed: dev0 [6,8] + [13,14] = 3 (dev1 [7,8] = 1)
    assert r.exposed_s == pytest.approx(3.0)
    assert r.n_collective_ops == 3
    assert r.device_ops[0] == ["fusion.2", pytest.approx(6.0)]
    # gaps where no device is busy: [16,20] (3 s of it in the wait span) and
    # [0,2] (1 s in each span; the first wins the tie)
    assert r.idle_gaps == [["bench.wait", pytest.approx(4.0)],
                           ["bench.dispatch", pytest.approx(2.0)]]


def test_collective_by_instruction_name_set():
    t = trace()
    t.ops["/device:TPU:1"].append(("psum.3", 16.0, 17.0))
    t.collectives = {"psum.3"}
    r = devtrace.reduce(t)
    assert r.n_collective_ops == 4
    assert r.exposed_s == pytest.approx(3.0)        # dev1: [7,8] + [16,17] = 2 < 3


def test_reduce_clips_to_host_window():
    t = trace()
    t.ops["/device:TPU:0"].append(("fusion.9", 19.5, 25.0))
    r = devtrace.reduce(t)
    assert r.busy_s == pytest.approx(12.75)      # only 0.5 s of fusion.9 is inside


def test_reduce_without_ops_refuses():
    with pytest.raises(ValueError):
        devtrace.reduce(devtrace.Trace(ops={}, host=[]))


def test_metric_readers_on_the_reduced_trace():
    from bench import harness

    run = harness.Run(setup_s=1.0, compile_s=0.5, peak={"hbm_bytes_per_s": 100.0},
                      trace=devtrace.reduce(trace()), traced_iterations=4,
                      least_bytes_per_iter=210.0)
    read = lambda m: harness.load_module("metrics", m).read(run)
    assert read("idle_share") == pytest.approx(37.5)
    assert read("collective_ms_per_iter") == pytest.approx(1000.0)
    assert read("collective_exposed_ms_per_iter") == pytest.approx(750.0)
    # 210 B * 4 iterations over 10.5 s * 100 B/s
    assert read("iteration_roofline") == pytest.approx(80.0)
    run.trace.n_collective_ops = 0
    assert read("collective_ms_per_iter") is None
    run.trace = None
    assert read("idle_share") is None and read("iteration_roofline") is None
