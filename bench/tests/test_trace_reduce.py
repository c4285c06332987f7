"""The trace reduction: busy and idle time, top operations, idle gaps
named by the host span that covers them.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import time

import pytest

from bench.trace_reduce import (Event, Trace, find_xplane, load,
                                reduce_trace, short_name)


def _synthetic():
    # window 0..1000 ns; on the device a loop runs 100..400 with two ops
    # nested in it, then one op runs 700..800; the host polls 50..450,
    # sleeps 450..690 and submits 690..1000
    host = [Event("bench.window", 0, 1000), Event("bench.poll", 50, 400),
            Event("bench.sleep", 450, 240), Event("bench.submit", 690, 310)]
    dev = [Event("%while.1 while u32[8,64]", 100, 300),
           Event("fusion.ntt", 120, 180), Event("fusion.crt", 300, 80),
           Event("fusion.ntt", 700, 100),
           Event("fusion.ntt", 1500, 100)]        # after the window
    return Trace(host=host, devices={"/device:TPU:0": dev})


def test_busy_idle_and_top_ops():
    red = reduce_trace(_synthetic())
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(400e-9)     # 100..400, 700..800
    assert red["idle_share"] == pytest.approx(0.6)
    # each op's own time: the loop less what ran nested in it
    assert red["device_ops"] == [["fusion.ntt", pytest.approx(280e-9)],
                                 ["fusion.crt", pytest.approx(80e-9)],
                                 ["%while.1 while u32[8,64]",
                                  pytest.approx(40e-9)]]


def test_tpu_op_names_are_shortened():
    line = ("%while.395 = (u32[]{:T(128)}, u32[122,524288]{1,0:T(8,128)}) "
            "while((u32[]{:T(128)}) %tuple.2662), condition=%c, body=%b")
    assert short_name(line) == "%while.395 while u32[122,524288]"
    assert short_name("%copy.1 = u32[81,1]{1,0} copy(u32[81,1]{0,1} %g)") \
        == "%copy.1 copy u32[81,1]"
    assert short_name("wrapped_sine") == "wrapped_sine"


def test_gaps_named_by_covering_host_span():
    red = reduce_trace(_synthetic())
    # gaps: 0..100 (poll covers 50..100), 400..700 (sleep 450..690),
    # 800..1000 (submit)
    assert red["idle_gaps"] == [["bench.sleep", pytest.approx(300e-9)],
                                ["bench.submit", pytest.approx(200e-9)],
                                ["bench.poll", pytest.approx(100e-9)]]
    assert red["idle_by_span"]["bench.sleep"] == pytest.approx(300e-9)


def test_two_devices_are_averaged():
    tr = _synthetic()
    tr.devices["/device:TPU:1"] = [Event("fusion.ntt", 0, 1000)]
    red = reduce_trace(tr)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((400e-9 + 1000e-9) / 2)


def test_no_window_or_no_device_work_is_an_error():
    tr = _synthetic()
    with pytest.raises(ValueError, match="window"):
        reduce_trace(Trace(host=tr.host[1:], devices=tr.devices))
    with pytest.raises(ValueError, match="no device operation"):
        reduce_trace(Trace(host=tr.host, devices={"/device:TPU:0": [
            Event("fusion.ntt", 2000, 10)]}))


def test_recorded_cpu_trace(tmp_path):
    """A real profiler trace: the CPU backend's XLA ops stand in for a
    device, and the idle gaps land in the benchmark's sleep span."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    red = reduce_trace(load(find_xplane(str(tmp_path))))
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] >= 0.06
    assert red["idle_gaps"][0][0] == "bench.sleep"
    assert red["idle_by_span"]["bench.sleep"] >= 0.05
    assert red["device_ops"]
