"""The trace reduction on hand-built traces, and the peaks table."""

import pathlib
import re
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import peaks, readers, trace  # noqa: E402

MS = 1_000_000  # ns


def _two_devices():
    """Window 0..100 ms.  Device 0 runs a GEMM 0-40, an all-gather-done
    40-50, the DMA kernel 60-70 and a GEMM that overlaps it 65-80;
    device 1 runs one GEMM 10-90 and a reduce-scatter 90-95."""
    dev0 = [(0, 40 * MS, "fusion.1"),
            (40 * MS, 50 * MS, "%all-gather-done.3 = bf16[4] all-gather-done"),
            (60 * MS, 70 * MS, "%shard_map.7 = bf16[4,1024,4096] custom-call("
             'bf16[1024,4096] %x), custom_call_target="tpu_custom_call", o'),
            (65 * MS, 80 * MS, "fusion.1")]
    dev1 = [(10 * MS, 90 * MS, "fusion.1"),
            (90 * MS, 95 * MS, "%reduce-scatter.2 = bf16[8] reduce-scatter")]
    spans = [(0, 100 * MS, trace.WINDOW_SPAN),
             (0, 55 * MS, "bench/train_step"),
             (52 * MS, 58 * MS, "bench/wait"),
             (80 * MS, 100 * MS, "bench/wait")]
    return trace.from_events({0: dev0, 1: dev1}, spans)


def test_busy_is_the_union_of_op_intervals_averaged_over_devices():
    t = _two_devices()
    # device 0: 0-50 and 60-80 -> 70 ms; device 1: 10-95 -> 85 ms
    assert t.window_s == pytest.approx(0.1)
    assert trace.busy_s(t) == pytest.approx((0.070 + 0.085) / 2)
    assert trace.idle_share(t) == pytest.approx(1 - 0.0775 / 0.1)


def test_ops_outside_the_window_are_clipped():
    t = trace.from_events(
        {0: [(-10 * MS, 10 * MS, "fusion.1"), (90 * MS, 120 * MS, "f.2")]},
        [(0, 100 * MS, trace.WINDOW_SPAN)])
    assert trace.busy_s(t) == pytest.approx(0.020)


def test_exposed_communication_counts_done_waits_collectives_and_dma():
    t = _two_devices()
    comm = _metric("exposed_comm_share.prefill").COMM
    # device 0: all-gather-done 10 ms + DMA kernel 10 ms; device 1:
    # reduce-scatter 5 ms -> mean 12.5 ms of a 100 ms window
    assert trace.busy_s(t, comm) == pytest.approx(0.0125)
    for other in ("%all-gather-start.3 = (bf16[4]) all-gather-start",
                  "%copy-done.1 = bf16[4] copy-done", "fusion.1",
                  "%all-reduce-fusion.2 = bf16[4] fusion"):
        assert not comm.search(other), other
    # op texts as a TPU v5e trace of the prefill cell prints them
    for name in ("%all-reduce.15 = bf16[4,4096] all-reduce",
                 "%all-gather.21 = bf16[4,4096,4096]{2,1,0:T(8,128)(2,1)} "
                 "all-gather(bf16[4,1024,4096] %fusion.228), channel_id=3",
                 "%collective-permute-done = bf16[4] collective-permute-done",
                 "%fusion.259 = bf16[4,1024,4096]{2,1,0} fusion(bf16[4,4096,"
                 "4096] %fusion.258), kind=kCustom, calls=%all-reduce-scatter"
                 ".1.clone"):
        assert comm.search(name), name
    assert not comm.search("%fusion.258 = bf16[4,4096,4096] fusion(bf16[4,"
                           "4096,2752] %x), kind=kOutput, calls=%fused.69")


def test_kernel_time_by_name_per_step():
    t = _two_devices()
    kernel = _metric("dma_exchange_ms.prefill").KERNEL
    secs, count = trace.op_seconds(t, kernel)
    assert secs == pytest.approx(0.010 / 2) and count == 1

    class Run:
        records = {"steps": 2}
        trace = t

    # 5 ms a device, averaged, over 2 calls
    assert readers.ms_per_step(Run, kernel) == pytest.approx(2.5)
    assert readers.ms_per_step(Run, re.compile("no-such-op")) is None


# As a TPU v5e trace of the prefill cell prints the exchange kernel.
EXCHANGE_OP = (
    "%shard_map.72 = bf16[4,1024,4096]{2,1,0:T(8,128)(2,1)} custom-call("
    "bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} %squeeze.268), custom_call_"
    'target="tpu_custom_call", operand_layout_constraints={bf16[1024,4096]'
    "{1,0}}, custom_call_has_side_effect=true")
OTHER_PALLAS = (
    # the fused AG->GEMM: a chunk and a weight panel
    "%shard_map.9 = bf16[4096,2816]{1,0} custom-call(bf16[1024,4096]{1,0} "
    "%x, bf16[4096,2816]{1,0} %w), custom_call_target=\"tpu_custom_call\"",
    # an attention kernel: q, k and v
    "%custom-call.3 = bf16[4,8,4096,128]{3,2,1,0} custom-call(bf16[4,8,4096,"
    "128]{3,2,1,0} %q, bf16[4,1,4096,128]{3,2,1,0} %k, bf16[4,1,4096,128]"
    '{3,2,1,0} %v), custom_call_target="tpu_custom_call"',
    # one operand, but not a gather of it
    "%custom-call.4 = bf16[1024,4096]{1,0} custom-call(bf16[1024,4096]{1,0} "
    '%x), custom_call_target="tpu_custom_call"',
    "%custom-call.5 = f32[4,1024,4096]{2,1,0} custom-call(bf16[1024,4096]"
    '{1,0} %x), custom_call_target="tpu_custom_call"',
)


@pytest.mark.parametrize("metric", ["dma_exchange_ms.prefill",
                                    "exposed_comm_share.prefill"])
def test_only_the_exchange_kernel_counts_as_exchange(metric):
    mod = _metric(metric)
    pattern = mod.KERNEL if hasattr(mod, "KERNEL") else mod.COMM
    assert pattern.search(EXCHANGE_OP)
    for name in OTHER_PALLAS:
        assert not pattern.search(name), name
    # a window whose only Pallas kernels compute reads as no exchange
    t = trace.from_events(
        {0: [(i * MS, (i + 1) * MS, n) for i, n in enumerate(OTHER_PALLAS)]},
        [(0, 10 * MS, trace.WINDOW_SPAN)])

    class Run:
        records = {"steps": 1}
        trace = t

    assert mod.read(Run) is None


def test_top_ops_and_idle_gaps_named_by_host_span():
    t = _two_devices()
    ops = dict(trace.top_ops(t))
    assert ops["fusion.1"] == pytest.approx((0.055 + 0.080) / 2)
    gaps = dict(trace.idle_gaps(t))
    # device 0 idles 50-60 (host in bench/wait at 55) and 80-100 (wait);
    # device 1 idles 0-10 (train_step) and 95-100 (wait)
    assert gaps["bench/wait"] == pytest.approx((0.010 + 0.020 + 0.005) / 2)
    assert gaps["bench/train_step"] == pytest.approx(0.010 / 2)


def test_idle_gaps_take_the_innermost_of_nested_spans():
    # an engine run holding two steps; the device idles inside the first
    # step (12-14), between the steps (20-30) and after them (40-100)
    t = trace.from_events(
        {0: [(0, 12 * MS, "a"), (14 * MS, 20 * MS, "b"),
             (30 * MS, 40 * MS, "c")]},
        [(0, 100 * MS, trace.WINDOW_SPAN), (0, 60 * MS, "bench/run"),
         (10 * MS, 20 * MS, "bench/step"), (30 * MS, 40 * MS, "bench/step")])
    gaps = dict(trace.idle_gaps(t))
    assert gaps == {"bench/step": pytest.approx(0.002),
                    "bench/run": pytest.approx(0.010),
                    "outside any benchmark span": pytest.approx(0.060)}


def test_top_ops_count_self_time_of_nested_events():
    # a while loop 0-50 ms whose body runs two fusions, 5-20 and 30-45
    ops = [(0, 50 * MS, "%while.1 = (...)"), (5 * MS, 20 * MS, "fusion.2"),
           (30 * MS, 45 * MS, "fusion.2"), (60 * MS, 70 * MS, "copy.3")]
    t = trace.from_events({0: ops}, [(0, 100 * MS, trace.WINDOW_SPAN)])
    top = dict(trace.top_ops(t))
    assert top == pytest.approx({"fusion.2": 0.030, "%while.1 = (...)": 0.020,
                                 "copy.3": 0.010})
    assert trace.busy_s(t) == pytest.approx(0.060)


def test_a_trace_needs_one_window_and_a_device():
    with pytest.raises(ValueError):
        trace.from_events({0: []}, [])
    with pytest.raises(ValueError):
        trace.from_events({}, [(0, 1, trace.WINDOW_SPAN)])


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def _metric(name):
    import importlib.util

    path = pathlib.Path(__file__).resolve().parents[1] / "metrics" / (
        name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
