"""The trace reduction on a synthetic trace with known answers."""

import pytest

import trace_reduce as tr
from networks import dense


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 8)]) == [
        (0, 4), (5, 7)]


def _trace():
    # window 0..100 ns from the spans; device 0 busy [10,30)+[25,40)
    # (union 30) + [60,70) = 40 ns; device 1 busy [0,100) = 100 ns
    spans = [("gen.submit", 0, 20), ("engine.step", 20, 80),
             ("bench.record", 80, 100)]
    devices = {
        "/device:TPU:0": [("_stack_kernel.1", 10, 30), ("copy", 25, 40),
                          ("_stack_kernel.1", 60, 70),
                          ("late", 150, 160)],          # outside: clipped
        "/device:TPU:1": [("_stack_kernel.1", 0, 100)],
    }
    return tr.Trace(devices=devices, spans=spans)


def test_busy_kernel_and_gaps():
    s = tr.reduce(_trace())
    assert s.window_s == pytest.approx(100e-9)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((40 + 100) / 2 * 1e-9)
    secs, calls, ops = tr.kernel_time(s, lambda name, _: "_stack" in name)
    assert calls == 3 and ops == ["_stack_kernel.1"]
    assert secs == pytest.approx((20 + 10 + 100) * 1e-9)
    # device 0 idle: [0,10) in gen.submit, [40,60) in engine.step,
    # [70,100) cut at 80: [70,80) in engine.step, [80,100) in
    # bench.record; device 1 never idle
    assert s.idle_by_span == pytest.approx(
        {"gen.submit": 5e-9, "engine.step": 15e-9, "bench.record": 10e-9})
    b = tr.breakdown(s)
    assert b["device_ops"][0][0] == "_stack_kernel.1"
    assert [g[0] for g in b["idle_gaps"]] == [
        "engine.step", "bench.record", "gen.submit"]


def test_program_spans_take_the_gap_and_leave_the_window():
    # engine.step [20, 80) holds the program's snn.step [22, 78), which
    # holds snn.readback [25, 45) and snn.upload [50, 70); a program span
    # that reaches past the harness's spans does not widen the window
    spans = [("gen.submit", 0, 20), ("engine.step", 20, 80),
             ("bench.record", 80, 100), ("snn.step", 22, 78),
             ("snn.readback", 25, 45), ("snn.upload", 50, 70),
             ("snn.dispatch", 95, 130)]
    devices = {"/device:TPU:0": [("op", 0, 21), ("op", 40, 52),
                                 ("op", 75, 100)]}
    s = tr.reduce(tr.Trace(devices=devices, spans=spans))
    bare = tr.reduce(tr.Trace(devices=devices, spans=spans[:3]))
    assert s.window_s == bare.window_s == pytest.approx(100e-9)
    assert s.busy_s == bare.busy_s == pytest.approx(58e-9)
    # gap [21, 40): engine.step [21,22), snn.step [22,25), snn.readback
    # [25,40); gap [52, 75): snn.upload [52,70), snn.step [70,75)
    assert s.idle_by_span == pytest.approx(
        {"engine.step": 1e-9, "snn.step": 8e-9, "snn.readback": 15e-9,
         "snn.upload": 18e-9})
    assert bare.idle_by_span == pytest.approx({"engine.step": 42e-9})
    assert [g[0] for g in tr.breakdown(s)["idle_gaps"]][:2] == [
        "snn.upload", "snn.readback"]


def test_spans_that_start_together_give_the_gap_to_the_inner():
    t = tr.Trace(devices={"/device:TPU:0": [("op", 0, 10), ("op", 30, 40)]},
                 spans=[("snn.sync", 10, 20), ("engine.step", 0, 40),
                        ("snn.step", 10, 30)])
    assert tr.reduce(t).idle_by_span == pytest.approx(
        {"snn.sync": 10e-9, "snn.step": 10e-9})


def test_gap_outside_every_span_is_none():
    t = tr.Trace(devices={"/device:TPU:0": [("op", 0, 5), ("op", 8, 20),
                                            ("op", 30, 50)]},
                 spans=[("engine.step", 0, 20), ("engine.step", 30, 50)])
    # gap [5, 8) lies in the first step, gap [20, 30) between the two
    assert tr.reduce(t).idle_by_span == pytest.approx(
        {"engine.step": 3e-9, "none": 10e-9})


def test_reduce_refuses_a_trace_without_spans_or_devices():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(devices={"/device:TPU:0": []}, spans=[]))
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(devices={}, spans=[("engine.step", 0, 1)]))


def _roofline_reader():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(tr.__file__), "metrics",
                        "stack_kernel_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# As a v5e compile of the engine's chunk names them: the kernel is the
# launcher's one custom call; padding and weight packing are ops of their
# own under the launcher's op path, and so is the consumer of its output.
KERNEL = ("fused_snn_stack_op.1",
          '%fused_snn_stack_op.1 = (s32[64,128]{1,0:T(8,128)S(1)}) '
          'custom-call(%pad.24, %pad_add_fusion), '
          'custom_call_target="tpu_custom_call" '
          'jit(stream_chunk)/jit(stream_chunk)/jit(fused_snn_stack_op)/'
          'pallas_call')
PACK = ("pad_add_fusion",
        '%pad_add_fusion = s8[2,896,128] fusion(%w) '
        'jit(stream_chunk)/jit(fused_snn_stack_op)/pad')
SLICE = ("slice_fusion",
         '%slice_fusion = s32[64,10] fusion(%fused_snn_stack_op.1) '
         'jit(stream_chunk)/jit(fused_snn_stack_op)/slice')


@pytest.mark.parametrize("name,text,is_kernel", [
    KERNEL + (True,), PACK + (False,), SLICE + (False,),
    ("fusion.3", "%fusion.3 = custom-call() tpu_custom_call "
     "jit(other)/pallas_call", False),
    ("k", "%k = custom-call() _stack_kernel", True),
    # as a v5e trace names them: the whole instruction, no stats
    ("%fused_snn_stack_op.1 = (s32[64,128]{1,0:T(8,128)S(1)}) "
     "custom-call(%pad.24, %pad_add_fusion), "
     "custom_call_target=\"tpu_custom_call\"", "", True),
    ("%slice_fusion = s32[64,10] fusion(%fused_snn_stack_op.1)", "", False),
    ("%copy.1 = s32[10]{0:T(128)} copy(s32[10]{0:T(128)} %args_0_.1)", "",
     False)])
def test_only_the_stack_kernels_custom_call_matches(name, text, is_kernel):
    assert _roofline_reader().is_stack_kernel(name, text) is is_kernel


def test_roofline_counts_launches_not_events():
    from types import SimpleNamespace

    events = [(KERNEL[0], 0, 40), (PACK[0], 40, 45), (SLICE[0], 45, 50),
              (KERNEL[0], 100, 140), (PACK[0], 140, 145)]
    t = tr.reduce(tr.Trace(
        devices={"/device:TPU:0": events}, spans=[("engine.step", 0, 200)],
        op_text=dict([KERNEL[:2], PACK[:2], SLICE[:2]])))
    mod = _roofline_reader()
    run = SimpleNamespace(
        trace=t, peaks={"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9},
        window=SimpleNamespace(step_calls=2, busy_lanes=128), launches=2,
        config={"mesh": {"data": 1, "model": 1}, "layer_sizes": [784, 10]},
        network=dense, chunk_steps=4, notes=[])
    # least time of a 64-lane paper launch: 541,888 B at 819 GB/s; the
    # kernel's own 80 ns, not the packing's or the slice's
    least = 2 * 7840 / 819e9 + 2 * 64 * 4111 / 819e9
    assert mod.read(run) == pytest.approx(100 * 2 * least / 80e-9)
    assert "memory-bound" in run.notes[0]
    # launches come from the dispatches: a kernel seen twice as often in
    # the trace cannot raise the share
    run.launches, run.notes = 1, []
    assert mod.read(run) == pytest.approx(100 * least / 80e-9)
    run.launches = None
    assert mod.read(run) is None
