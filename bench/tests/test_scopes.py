"""The attribution of device time to the solve's layers (``bench/scopes.py``)
on a hand-written HLO module and a synthetic two-device trace, whose
answers are worked out by hand."""

import pytest

from bench import devtrace, harness, scopes

HLO = """\
HloModule jit_solve, entry_computation_layout={(bf16[8]{0})->bf16[8]{0}}

%fused_spmv (p0: bf16[8], p1: bf16[8]) -> bf16[8] {
  %p0 = bf16[8]{0} parameter(0)
  %p1 = bf16[8]{0} parameter(1)
  %mul.1 = bf16[8]{0} multiply(%p0, %p1), metadata={op_name="jit(f)/while/body/spmv/mul"}
  ROOT %add.1 = bf16[8]{0} add(%mul.1, %p0), metadata={op_name="jit(f)/while/body/dots/add"}
}

%fused_update (p0: bf16[8], p1: bf16[8]) -> (bf16[8], f32[]) {
  %p0 = bf16[8]{0} parameter(0)
  %p1 = bf16[8]{0} parameter(1)
  %add.3 = bf16[8]{0} add(%p0, %p1), metadata={op_name="jit(f)/while/body/update/add"}
  %dot.1 = f32[] dot(%add.3, %add.3), metadata={op_name="jit(f)/while/body/dots/dot_general"}
  ROOT %t = (bf16[8]{0}, f32[]) tuple(%add.3, %dot.1)
}

%body (arg: (s32[], bf16[8], bf16[8])) -> (s32[], bf16[8], bf16[8]) {
  %arg = (s32[], bf16[8]{0}, bf16[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = bf16[8]{0} get-tuple-element(%arg), index=1
  %gte.2 = bf16[8]{0} get-tuple-element(%arg), index=2
  %copy.1 = bf16[8]{0} copy(%gte.2)
  %constant.1 = bf16[] constant(0)
  %broadcast.9 = bf16[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(f)/broadcast_in_dim"}
  %fusion.1 = bf16[8]{0} fusion(%gte.1, %broadcast.9), kind=kLoop, calls=%fused_spmv, metadata={op_name="jit(f)/while/body/dots/add"}
  %pad.1 = bf16[10]{0} pad(%fusion.1, %constant.1), padding=1_1, metadata={op_name="jit(f)/while/body/spmv/halo/jit(_pad)/pad"}
  %fusion.3 = (bf16[8]{0}, f32[]) fusion(%copy.1, %fusion.1), kind=kLoop, calls=%fused_update, metadata={op_name="jit(f)/while/body/dots/dot_general"}
  %gte.3 = bf16[8]{0} get-tuple-element(%fusion.3), index=0, metadata={op_name="jit(f)/while/body/dots/dot_general"}
  %gte.4 = f32[] get-tuple-element(%fusion.3), index=1
  %all-reduce.1 = f32[] all-reduce(%gte.4), replica_groups={}, to_apply=%sum, metadata={op_name="jit(f)/while/body/dots/psum"}
  %divide.1 = f32[] divide(%all-reduce.1, %all-reduce.1), metadata={op_name="jit(f)/while/body/div"}
  %one = s32[] constant(1)
  %add.2 = s32[] add(%gte.0, %one), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.1 = (s32[], bf16[8]{0}, bf16[8]{0}) tuple(%add.2, %fusion.1, %gte.3)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%cond (arg: (s32[], bf16[8], bf16[8])) -> pred[] {
  %arg = (s32[], bf16[8]{0}, bf16[8]{0}) parameter(0)
  %gte.9 = s32[] get-tuple-element(%arg), index=0
  %ten = s32[] constant(10)
  ROOT %lt.1 = pred[] compare(%gte.9, %ten), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %fusion.0 = bf16[8]{0} fusion(%p, %p), kind=kLoop, calls=%fused_spmv, metadata={op_name="jit(f)/spmv/mul"}
  %copy.0 = bf16[8]{0} copy(%fusion.0)
  %tuple.0 = (s32[], bf16[8]{0}, bf16[8]{0}) tuple(%zero, %fusion.0, %copy.0)
  %while.1 = (s32[], bf16[8]{0}, bf16[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %out = bf16[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_parse():
    comps, entry = scopes.parse_hlo(HLO)
    assert entry == "main"
    assert set(comps) == {"fused_spmv", "fused_update", "body", "sum", "cond", "main"}
    fusion = {i.name: i for i in comps["body"]}["fusion.3"]
    assert (fusion.opcode, fusion.operands, fusion.called) == ("fusion", ["copy.1", "fusion.1"],
                                                               ["fused_update"])
    assert fusion.elements == 8
    assert {i.name: i for i in comps["body"]}["gte.3"].index == 0
    assert [i.name for i in comps["body"] if i.root] == ["tuple.1"]


def test_layers_by_hand():
    layers = scopes.layer_map(HLO)
    # the entry computation, outside the loop, whatever its metadata says
    assert layers["fusion.0"] == layers["copy.0"] == layers["while.1"] == "setup"
    # a fusion takes its highest-ranked fused scope: spmv over dots ...
    assert layers["fusion.1"] == "spmv"
    # ... and update over dots (an AXPY pass with a dot epilogue)
    assert layers["fusion.3"] == "update"
    # the innermost scope: a halo pad inside the SpMV is the halo's
    assert layers["pad.1"] == "halo"
    assert layers["all-reduce.1"] == "dots"
    # the loop's own arithmetic that no scope claims
    assert layers["divide.1"] == layers["add.2"] == layers["lt.1"] == "unscoped"
    # added by the compiler: a copy of the carry takes the layer that
    # produced it last iteration (root operand 2 is gte.3 of fusion.3) ...
    assert layers["copy.1"] == "update"
    # ... and a constant buffer sunk into the loop that of its reader
    assert layers["broadcast.9"] == "spmv"
    assert scopes.unscoped_vectors(HLO, 8) == []
    assert scopes.unscoped_vectors(HLO, 1) == ["divide.1", "add.2", "lt.1"]


def test_scope_of_is_innermost():
    assert scopes.scope_of("jit(f)/while/body/spmv/halo/jit(_pad)/pad") == "halo"
    assert scopes.scope_of("jit(f)/while/body/update/jit(update_p)/update_p/pallas_call") == "update"
    assert scopes.scope_of("jit(f)/while/body/div") is None
    assert scopes.scope_of("") is None


def test_pallas_kernels_by_custom_call_name():
    text = HLO.replace(
        '  %one = s32[] constant(1)\n',
        '  %one = s32[] constant(1)\n'
        '  %update_p.7 = bf16[8]{0} custom-call(%gte.1), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(f)/while/body/update/jit(update_p)/update_p/pallas_call"}\n')
    assert scopes.pallas_kernels(text) == ["update_p"]
    assert scopes.layer_map(text)["update_p.7"] == "update"
    assert scopes.pallas_kernels(HLO) == []


LAYERS = {"fusion.0": "setup", "fusion.1": "spmv", "pad.1": "halo", "fusion.3": "update",
          "all-reduce.1": "dots", "reduce.1": "dots", "divide.1": "unscoped",
          "collective-permute-done.1": "halo"}


def capture():
    """Two devices, host spans from 0 to 20 s, two solves (programs) on
    each device, 4 iterations in all."""
    host = [("bench.dispatch", 0.0, 1.0), ("bench.wait", 1.0, 9.0), ("bench.read", 9.0, 10.0),
            ("bench.dispatch", 10.0, 11.0), ("bench.wait", 11.0, 19.0), ("bench.read", 19.0, 20.0)]
    dev0 = [("fusion.0", 1.0, 2.0), ("fusion.1", 2.0, 4.0), ("pad.1", 4.0, 4.5),
            ("fusion.3", 4.5, 6.0), ("all-reduce.1", 6.0, 6.5), ("reduce.1", 6.5, 7.0),
            ("divide.1", 7.0, 7.5),
            ("fusion.0", 11.0, 12.0), ("fusion.1", 12.0, 14.0), ("fusion.3", 14.0, 15.0),
            ("all-reduce.1", 15.0, 16.0), ("fusion.9", 16.0, 16.5),
            ("collective-permute-done.1", 16.0, 16.4)]
    dev1 = [("fusion.0", 1.0, 2.0), ("fusion.1", 2.0, 5.0), ("fusion.3", 5.0, 6.0),
            ("all-reduce.1", 6.0, 6.2),
            ("fusion.0", 11.0, 12.0), ("fusion.1", 12.0, 15.0), ("fusion.3", 15.0, 16.0)]
    trace = devtrace.Trace(ops={"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host=host,
                           collectives={"all-reduce.1", "collective-permute-done.1"})
    programs = {"/device:TPU:0": [(1.0, 7.5, 11), (11.0, 16.5, 12)],
                "/device:TPU:1": [(1.0, 6.2, 11), (11.0, 16.0, 12)]}
    launches = {11: 0.5, 12: 10.25}
    return scopes.Capture(trace, programs, launches, {d: 0.0 for d in programs},
                          {"all-reduce.1"})


def test_layer_times_by_hand():
    t = scopes.layer_times(capture(), LAYERS)
    # non-collective union per layer, mean over the two devices
    assert t["compute"]["spmv"] == pytest.approx((4 + 6) / 2)        # dev0 2+2, dev1 3+3
    assert t["compute"]["update"] == pytest.approx((2.5 + 2) / 2)
    assert t["compute"]["halo"] == pytest.approx(0.5 / 2)
    assert t["compute"]["dots"] == pytest.approx(0.5 / 2)            # reduce.1, not the all-reduce
    assert t["compute"]["setup"] == pytest.approx(2.0)
    assert t["compute"]["unscoped"] == pytest.approx((0.5 + 0.5) / 2)  # divide.1 and fusion.9
    # the all-reduces of ``dots``, on the device with most: dev0 0.5 + 1;
    # a halo permute is neither compute nor an all-reduce
    assert t["allreduce"] == {"dots": pytest.approx(1.5)}
    assert t["ops"]["halo"]["collective-permute-done.1"] == pytest.approx(0.4 / 2)


def test_readings_by_hand():
    r = scopes.readings(capture(), LAYERS, iterations=4, solves=2,
                        least_spmv_bytes_per_iter=100.0, hbm_bytes_per_s=50.0)
    assert r["spmv_ms_per_iter"] == pytest.approx(1e3 * 5 / 4)
    assert r["update_ms_per_iter"] == pytest.approx(1e3 * 2.25 / 4)
    assert r["dots_ms_per_iter"] == pytest.approx(1e3 * 0.25 / 4)
    assert r["halo_ms_per_iter"] == pytest.approx(1e3 * 0.25 / 4)
    assert r["reduce_ms_per_iter"] == pytest.approx(1e3 * 1.5 / 4)
    assert r["setup_ms_per_solve"] == pytest.approx(1e3 * 2 / 2)
    # 100 B * 4 iterations over 5 s of SpMV at 50 B/s
    assert r["spmv_roofline"] == pytest.approx(160.0)
    # loop: spmv 5, update 2.25, halo .25, dots .25, unscoped .5
    assert r["unscoped_share"] == pytest.approx(100 * 0.5 / 8.25)
    # gaps between programs: dev0 [7.5, 11], dev1 [6.2, 11]; per solve, mean over devices
    assert r["host_gap_ms_per_solve"] == pytest.approx(1e3 * (3.5 + 4.8) / 2 / 2)


def test_readings_without_scopes_read_nothing():
    """A program built before the scopes: every op of the loop unscoped."""
    flat = {k: ("setup" if v == "setup" else "unscoped") for k, v in LAYERS.items()}
    r = scopes.readings(capture(), flat, iterations=4, solves=2,
                        least_spmv_bytes_per_iter=100.0, hbm_bytes_per_s=50.0)
    for name in ("spmv_ms_per_iter", "update_ms_per_iter", "dots_ms_per_iter",
                 "halo_ms_per_iter", "reduce_ms_per_iter", "spmv_roofline", "unscoped_share"):
        assert r[name] is None, name


def test_host_gaps_and_pairing():
    cap = capture()
    gaps = scopes.host_gaps(cap)
    assert [(d[-1], s, e, span) for d, s, e, span in gaps] == [
        ("0", 7.5, 11.0, "bench.wait"), ("1", 6.2, 11.0, "bench.wait")]
    # each program started on the device 0.5 s and 0.75 s after its launch
    assert scopes.pair(cap) == {d: [pytest.approx(0.5), pytest.approx(0.75)]
                                for d in cap.programs}


def test_breakdown_partitions_the_compute_time():
    cap = capture()
    b = scopes.breakdown(cap, LAYERS, iterations=4, solves=2)
    red = devtrace.reduce(cap.trace)
    # layers that never overlap one another sum to the non-collective union
    assert b["partition"]["layers_plus_setup_ms_per_iter"] == pytest.approx(
        1e3 * red.compute_busy_s / 4)
    assert b["run_id_pairs"] == 4
    assert b["gaps_ms_by_host_span"]["bench.wait"]["count"] == 2


def test_existing_readers_read_as_before():
    """The capture's trace is the benchmark's: its readers give the values
    that ``test_devtrace.py`` works out by hand for the same intervals."""
    from bench.tests.test_devtrace import trace as devtrace_trace

    cap = scopes.Capture(devtrace_trace(), {}, {}, {})
    run = harness.Run(setup_s=1.0, compile_s=0.5, peak={"hbm_bytes_per_s": 100.0},
                      trace=devtrace.reduce(cap.trace), traced_iterations=4,
                      least_bytes_per_iter=210.0)
    read = lambda m: harness.load_module("metrics", m).read(run)
    assert read("idle_share") == pytest.approx(37.5)
    assert read("collective_ms_per_iter") == pytest.approx(1000.0)
    assert read("collective_exposed_ms_per_iter") == pytest.approx(750.0)
    assert read("iteration_roofline") == pytest.approx(80.0)


@pytest.mark.parametrize("n_offsets, words", [(6, 12), (24, 48)])
def test_spmv_least_words(n_offsets, words):
    count = harness.load_module("counts", "bicgstab")
    assert scopes.spmv_least_words_per_point(n_offsets) == words
    # the field term of the iteration's least count, which adds 8 vector words
    assert count.least_words_per_point(n_offsets) - words == 8


@pytest.mark.parametrize("name, mesh, chips, n_off, iters, seconds",
                         __import__("bench.tests.test_counts", fromlist=["READINGS"]).READINGS)
def test_spmv_least_bytes_under_peak(name, mesh, chips, n_off, iters, seconds):
    """The SpMV's floor stays under the HBM peak for every chip reading of
    the repository, even with the whole warm time given to the SpMV."""
    import math

    peak = harness.peak_of("TPU v5 lite")["hbm_bytes_per_s"]
    rate = scopes.spmv_least_words_per_point(n_off) * 2 * math.prod(mesh) / chips * iters / seconds
    assert rate < peak, f"{name}: {rate / 1e9:.1f} GB/s"


def _xspace(tmp_path):
    """A profiler file holding one device and one host plane: two solve
    programs, their ops, the benchmark's spans and the host's launch events
    that share each program's ``run_id``; times in microseconds."""
    from jax.profiler import ProfileData

    events, names = [], {}

    def ev(name, start_us, dur_us, run_id=None):
        mid = names.setdefault(name, len(names) + 1)
        stat = f" stats {{ metadata_id: 1 int64_value: {run_id} }}" if run_id else ""
        return (f"events {{ metadata_id: {mid} offset_ps: {int(start_us * 1e6)} "
                f"duration_ps: {int(dur_us * 1e6)}{stat} }}")

    def line(lid, name, evs):
        return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {" ".join(evs)} }}'

    def meta():
        return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                        for n, i in names.items()) + \
            ' stat_metadata { key: 1 value { id: 1 name: "run_id" } }'

    modules = [ev("jit__lambda_(5)", 10, 60, 101), ev("jit__lambda_(5)", 110, 60, 102)]
    ops = [ev("%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 12, 30),
           ev("%psum.3 = f32[2] all-reduce(f32[2] %b), channel_id=1", 42, 5),
           ev("copy.2", 47, 20),
           ev("%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 112, 50)]
    # on the asynchronous line, a permute in flight over the first fusion
    # and a short copy inside the second: neither contains an op of its
    # own line, so both are leaves
    async_ops = [ev("%collective-permute-start.4 = (bf16[8]) collective-permute-start("
                    "bf16[8] %a)", 11, 40),
                 ev("copy-start.5", 120, 2)]
    device = (f'planes {{ id: 1 name: "/device:TPU:0" {line(1, "XLA Modules", modules)} '
              f'{line(2, "XLA Ops", ops)} {line(4, "Async XLA Ops", async_ops)} {meta()} }}')
    names.clear()
    host_evs = [ev("bench.dispatch", 20, 5), ev("Execute", 20, 2, 101),
                ev("bench.wait", 25, 70), ev("bench.read", 95, 5),
                ev("bench.dispatch", 100, 5), ev("Execute", 111, 2, 102),
                ev("bench.wait", 105, 70), ev("bench.read", 175, 5), ev("solve.krylov", 100, 80)]
    host = f'planes {{ id: 2 name: "/host:CPU" {line(3, "python", host_evs)} {meta()} }}'
    path = tmp_path / "trace" / "t.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(device + " " + host))
    return str(tmp_path / "trace")


def test_load_reads_as_the_benchmark(tmp_path):
    d = _xspace(tmp_path)
    cap, ref = scopes.load(d), devtrace.load(d)
    # the device clock moves 10 us: the first program started before the
    # first dispatch span
    assert cap.trace.ops == ref.ops and cap.trace.host == ref.host
    assert {n for n, _, _ in cap.trace.ops["/device:TPU:0"]} == {
        "fusion.1", "psum.3", "copy.2", "collective-permute-start.4", "copy-start.5"}
    assert cap.trace.collectives == ref.collectives == {"psum.3", "collective-permute-start.4"}
    assert cap.allreduces == {"psum.3"}
    assert devtrace.reduce(cap.trace) == devtrace.reduce(ref)
    assert cap.shift == {"/device:TPU:0": pytest.approx(10e-6)}
    # on the shifted clock the first program starts with its launch, the
    # second 9 us after it
    assert scopes.pair(cap) == {"/device:TPU:0": [pytest.approx(0, abs=1e-12),
                                                  pytest.approx(9e-6)]}
    # the program's own spans, with other prefixes
    assert [h[0] for h in scopes.load(d, ("solve.",)).trace.host] == ["solve.krylov"]


def test_main_rereads_a_kept_trace(tmp_path, capsys):
    """``--hlo/--xplane/--meta`` read a kept pair again, off the chip."""
    import json

    d = _xspace(tmp_path)
    hlo = tmp_path / "solve.hlo.txt"
    hlo.write_text(HLO.replace("all-reduce.1", "psum.3"))
    meta = tmp_path / "meta.json"
    meta.write_text(json.dumps({"iterations": 2, "solves": 2,
                                "least_spmv_bytes_per_iter": 800e3,
                                "hbm_bytes_per_s": 819e9}))
    assert scopes.main(["--hlo", str(hlo), "--xplane", d, "--meta", str(meta)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = out["metrics"]
    # fusion.1 runs 30 + 50 us, over 2 iterations
    assert m["spmv_ms_per_iter"] == pytest.approx(0.04)
    assert m["spmv_roofline"] == pytest.approx(100 * 800e3 * 2 / (80e-6 * 819e9))
    # copy.2 and the copy in flight are in no layer the HLO knows
    assert m["unscoped_share"] == pytest.approx(100 * 22 / 102)
    assert out["breakdown"]["run_id_pairs"] == 2
    assert out["kernels"] == []
