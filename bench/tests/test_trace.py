"""The trace reduction, on a small trace recorded on a TPU v5e
(``bench/testdata/make_probe_trace.py``) and on hand-made planes."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.lib import trace

PROBE = Path(__file__).resolve().parents[1] / "testdata" / "probe.xplane.pb"


def test_recorded_trace():
    s = trace.reduce_file(str(PROBE))
    # no "window" span in the probe: the window is the device ops' span
    assert s["window_s"] == pytest.approx(0.02265439)
    assert s["busy_s"] == pytest.approx(0.000151393)
    assert set(s["programs"]) == {"jit__prefill_fn", "jit_counted_fn"}
    assert s["programs"]["jit__prefill_fn"] == pytest.approx(0.000106186)
    # the kernel is the one tpu_custom_call, inside the prefill program
    assert s["custom_calls"] == {"jit__prefill_fn": pytest.approx(8.6031e-05)}
    assert s["device_ops"][0] == ["jit__prefill_fn/flash_prefill_attention",
                                  pytest.approx(8.6031e-05)]
    labels = [g[0] for g in s["idle_gaps"]]
    assert labels[0] == "step" and "step+submit" in labels
    assert len(s["idle_gaps"]) <= 10 and len(s["device_ops"]) <= 10
    assert trace.breakdown(s) == {"device_ops": s["device_ops"],
                                  "idle_gaps": s["idle_gaps"]}


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


KERNEL = ('%latent_decode_attention.6 = bf16[8] custom-call(bf16[8] '
          '%custom-call.24), custom_call_target="tpu_custom_call"')
CONCAT = ('%custom-call.24 = bf16[8] custom-call(bf16[8] %x), '
          'custom_call_target="ConcatBitcast"')
FUSION = '%fusion.3 = bf16[8] fusion(bf16[8] %custom-call.24), kind=kLoop'


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("window", 100, 1000), ev("step", 100, 500), ev("submit", 650, 100)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_counted_fn(7)", 50, 450),
                                       ev("jit__prefill_fn(9)", 600, 300)]),
        NS(name="XLA Ops", events=[
            ev("%while.1 = (s32[]) while(s32[] %t)", 50, 450),  # holds:
            ev(KERNEL, 60, 200), ev(CONCAT, 300, 50), ev(FUSION, 400, 100),
            ev(FUSION, 600, 300)])])
    return [host, dev]


def test_nesting_window_and_kernels():
    s = trace.reduce_planes(planes())
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 500) from the loop clipped to the window, [600, 900)
    assert s["busy_s"] == pytest.approx(700e-9)
    assert s["programs"]["jit_counted_fn"] == pytest.approx(400e-9)
    # only the tpu_custom_call is a kernel; XLA's own custom call is not
    assert s["custom_calls"] == {"jit_counted_fn": pytest.approx(160e-9)}
    ops = dict((k, v) for k, v in s["device_ops"])
    # the loop's self time is its time less its body's ops
    assert ops["jit_counted_fn/while"] == pytest.approx((400 - 160 - 50 - 100)
                                                        * 1e-9)
    assert ops["jit__prefill_fn/fusion"] == pytest.approx(300e-9)
    gaps = s["idle_gaps"]
    assert gaps[0] == ["none", pytest.approx(200e-9)]     # [900, 1100)
    assert gaps[1] == ["step", pytest.approx(100e-9)]     # [500, 600)


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes([planes()[0]])
