"""The reduction from a trace to numbers, on a hand-made event list with
known overlaps and on a recorded trace."""

import glob
import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.total(tr.union([(0, 1), (0.5, 2)])) == 2
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1)], []) == [(0, 1)]
    assert tr.intersect([(0, 5), (7, 9)], [(4, 8)]) == [(4, 5), (7, 8)]


def test_own_time_subtracts_nested_children():
    events = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
              ("fusion.2", 5.0, 6.0), ("inner", 2.0, 3.0), ("after", 10.0, 12.0)]
    assert tr.own_times(events) == [6.0, 2.0, 1.0, 1.0, 2.0]


def hand_made():
    """One device, a window of 20 s. The op line:

        0-4     fusion.1                          xla_op
        4-6     flash_fwd, a Pallas custom call   attention_kernel
        6-6.5   all-reduce-start.1                collective
        6.5-8   fusion.2 (the all-reduce is in flight: hidden)
        8-9     all-reduce-done.1 (the wait: exposed)
        12-14   all-reduce.2 (synchronous, alone)
        14-15   copy.3
        18-20   fusion.3

    and beside it all-reduce-start.1 in flight 6-9. Busy union = 0-9,
    12-15, 18-20 = 14; idle = 6 in the gaps 9-12 and 15-18.
    """
    device = [("fusion %fusion.1", 0, 4), ("tpu_custom_call %flash_fwd.3", 4, 6),
              ("all-reduce-start %all-reduce-start.1", 6, 6.5),
              ("fusion %fusion.2", 6.5, 8),
              ("all-reduce-done %all-reduce-done.1", 8, 9),
              ("all-reduce %all-reduce.2", 12, 14), ("copy %copy.3", 14, 15),
              ("fusion %fusion.3", 18, 20)]
    in_flight = [("all-reduce-start %all-reduce-start.1", 6, 9),
                 ("slice-start %slice-start.4", 0, 20)]
    host = [("kv_gather_host", 9.5, 11.0), ("decode_call", 11.0, 12.5),
            ("kv_gather_host", 16.0, 17.0), ("other", 0.0, 20.0)]
    return tr.Trace(devices={"/device:TPU:0": device},
                    in_flight={"/device:TPU:0": in_flight}, host=host)


def test_hand_made_trace_pins_every_number():
    s = tr.summarize(hand_made(), units=2,
                     host_span_names=["kv_gather_host", "decode_call", "absent"])
    d = s.first
    assert d.window == (0, 20) and d.busy_s == 14 and s.window_s == 20
    assert d.idle_share == pytest.approx(0.3)
    assert d.gaps == [(9, 12), (15, 18)]
    assert d.class_s == {"xla_op": 7.5, "attention_kernel": 2.0,
                         "collective": 3.5, "copy": 1.0}
    assert sum(d.class_s.values()) == pytest.approx(d.busy_s)
    # in flight or running 6-9 and 12-14; fusion.2 hides 6.5-8 of it
    assert d.span_s["collective"] == pytest.approx(5.0)
    assert d.exposed_s["collective"] == pytest.approx(3.5)
    assert d.span_s["attention_kernel"] == d.exposed_s["attention_kernel"] == 2
    assert "xla_op" in d.span_s and len(d.span_s) == 4   # slice-start: no class
    # gaps: gather 9.5-11 and 16-17 = 2.5; decode_call 11-12 = 1; rest 2.5
    assert s.idle_by_host_span == {"kv_gather_host": pytest.approx(2.5),
                                   "decode_call": pytest.approx(1.0),
                                   tr.UNATTRIBUTED: pytest.approx(2.5)}
    assert sum(s.idle_by_host_span.values()) == pytest.approx(
        d.window_s - d.busy_s)
    b = s.breakdown(top=3)
    assert b["device_ops"][0] == ["fusion %fusion.1 [xla_op]", 4]
    assert len(b["device_ops"]) == 3 and b["idle_gaps"][0][1] == 2.5


def test_own_time_charges_a_partial_overlap_to_the_later_operation():
    events = [("a", 0.0, 4.0), ("b", 3.0, 6.0)]
    assert tr.own_times(events) == [3.0, 3.0]


def test_op_names():
    assert tr.op_name(
        '%block_6.3 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[128,1024,'
        '64]{2,1,0:T(8,128)(2,1)}) custom-call(s32[2]{0:T(128)} %copy-done.9),'
        ' custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == "tpu_custom_call %block_6.3"
    assert tr.op_name('%fusion.15 = (f32[50304,1024]{1,0:T(8,128)}, f32[8]{0})'
                      ' fusion(f32[1]{0} %x), kind=kLoop') == "fusion %fusion.15"
    assert tr.op_name('%copy-done.1 = bf16[1024]{0:T(1024)(128)(2,1)S(1)} '
                      'copy-done((bf16[1024]{0}, u32[]{:S(2)}) %copy-start.1)'
                      ) == "copy-done %copy-done.1"
    assert tr.op_name("all-reduce.1") == "all-reduce all-reduce.1"
    assert tr.op_name("dot") == "dot dot"


RECORDED = {
    # PR 22's chip run: the kernels had no name yet (``%block_0.2``), so
    # since the class was narrowed to the flash kernels' names they are
    # ``xla_op``, as any Pallas call is that no class file claims
    "tiny_train_v5e": dict(
        window_s=0.013793342, busy_s=0.000901259, idle_share=0.93466,
        class_s={"copy": 0.00025065, "xla_op": 0.000650609},
        span_copy=0.000703544, exposed_copy=0.000264553, kernels=[]),
    # PR 24's chip run of the same model, the kernels named
    "tiny_train_scoped_v5e": dict(
        window_s=0.013512116, busy_s=0.000899438, idle_share=0.93343,
        class_s={"copy": 0.000249539, "xla_op": 0.000506489,
                 "attention_kernel": 0.00014341},
        span_copy=None, exposed_copy=None,
        kernels=["tpu_custom_call %flash_bwd.2", "tpu_custom_call %flash_bwd.3",
                 "tpu_custom_call %flash_fwd.2", "tpu_custom_call %flash_fwd.3"]),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_v5e_trace_reduces_to_the_pinned_numbers(name):
    """15 steps of the rehearsal model (2 layers, width 128, batch 2 x 128)
    traced on a TPU v5 lite in a builder's own chip run: the file's
    numbers, as this reduction reads them."""
    want = RECORDED[name]
    trace = tr.read_xplane(os.path.join(DATA, f"{name}.xplane.pb.gz"))
    assert sorted(trace.devices) == ["/device:TPU:0"]
    s = tr.summarize(trace, units=15)
    d = s.first
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert d.idle_share == pytest.approx(want["idle_share"], rel=1e-4)
    assert d.class_s == {k: pytest.approx(v, rel=1e-5)
                         for k, v in want["class_s"].items()}
    assert sum(d.class_s.values()) == pytest.approx(d.busy_s)
    if want["span_copy"]:
        assert d.span_s["copy"] == pytest.approx(want["span_copy"], rel=1e-5)
        assert d.exposed_s["copy"] == pytest.approx(want["exposed_copy"],
                                                    rel=1e-5)
    # 2 layers x (forward, backward) x 15 steps, under four names
    assert sorted(n for n, c in d.op_class.items()
                  if c == "attention_kernel") == want["kernels"]
    assert sum(n.startswith("tpu_custom_call ") for n in d.op_class) == 4
    assert s.idle_by_host_span == {tr.UNATTRIBUTED: pytest.approx(
        d.window_s - d.busy_s)}


def test_busy_is_averaged_over_devices():
    t = hand_made()
    t.devices["/device:TPU:1"] = [("fusion %fusion.1", 0, 10),
                                  ("fusion %fusion.3", 18, 20)]
    s = tr.summarize(t, units=1)
    assert s.busy_s == pytest.approx((14 + 12) / 2)
    assert s.first.device == "/device:TPU:0"


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(), units=1)
