"""The program's spans and stage scopes in a window's trace
(`bench/program_trace.py`), and the metric readers built on them.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import time
from pathlib import Path

import pytest

from bench import program_trace as PT
from bench import run as R
from bench.tests.test_loaders import tiny_cell
from bench.trace_reduce import Event, find_xplane

ROOT = Path(__file__).resolve().parents[2]


def _nested(stages=None):
    # window 0..1000 ns; the benchmark's poll 100..900 holds the
    # program's poll 110..890, which holds dispatch 120..400 (h2d
    # 130..300 in it), wait 400..700 and retire 700..880. The step runs
    # 300..400 and a loop 450..700 with two ops nested in it; a result
    # slice (another program) runs 860..880.
    host = [Event("bench.window", 0, 1000), Event("bench.poll", 100, 800),
            Event("hserve.poll", 110, 780),
            Event("hserve.dispatch", 120, 280),
            Event("hserve.h2d", 130, 170), Event("hserve.wait", 400, 300),
            Event("hserve.retire", 700, 180)]
    ops = [(300, 100, "fusion.1", "jit_step(7)"),
           (450, 250, "while.2", "jit_step(7)"),
           (500, 100, "fusion.3", "jit_step(7)"),
           (600, 50, "copy.4", "jit_step(7)"),
           (860, 20, "fusion.1", "jit_squeeze(9)")]
    return PT.ProgramTrace(host=host, h2d=[(130, 4096), (1500, 4096)],
                           devices={"/device:TPU:0": ops},
                           stages=stages or {})


def test_idle_time_goes_to_the_innermost_program_span():
    red = PT.reduce_program(_nested())
    # 0..110 and 890..1000 no program span; 110..120 and 880..890 poll
    # alone; 120..130 dispatch; 130..300 h2d; 400..450 wait; 700..860
    # retire (the slice runs 860..880)
    assert red["idle_by_program_span"] == {
        k: pytest.approx(v * 1e-9) for k, v in {
            "none": 220, "hserve.h2d": 170, "hserve.retire": 160,
            "hserve.wait": 50, "hserve.poll": 20,
            "hserve.dispatch": 10}.items()}
    assert red["program_spans"]["hserve.h2d"] == {
        "s": pytest.approx(170e-9), "n": 1}
    assert red["program_spans"]["hserve.poll"]["s"] == pytest.approx(780e-9)
    assert "bench.poll" not in red["program_spans"]
    assert red["h2d_bytes"] == 4096            # the span after the window
    assert red["device_by_scope"] is None      # no program carries scopes


def test_idle_gaps_are_named_by_the_span_path():
    red = PT.reduce_program(_nested())
    assert red["idle_gaps"][0] == [
        "bench.poll/hserve.poll/hserve.dispatch/hserve.h2d",
        pytest.approx(300e-9)]
    assert ["bench.poll/hserve.poll/hserve.retire",
            pytest.approx(160e-9)] in red["idle_gaps"]


def test_a_device_plane_without_ops_is_no_device(tmp_path):
    """A TPU trace also holds device planes with no XLA ops (such as
    ``/device:CUSTOM:...``): they must not count as an idle device."""
    from jax.profiler import ProfileData
    space = ProfileData.text_proto_to_serialized_xspace('''
      planes { name: "/device:TPU:0" lines { name: "XLA Ops"
        events { metadata_id: 1 offset_ps: 100000 duration_ps: 400000 } }
        event_metadata { key: 1 value {
          id: 1 name: "%fusion.1 = u32[8] fusion(u32[8] %p)" } } }
      planes { name: "/device:CUSTOM:Megascale Trace" }
      planes { name: "/host:CPU" lines { name: "python"
        events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
        events { metadata_id: 2 offset_ps: 0 duration_ps: 800000 } }
        event_metadata { key: 1 value { id: 1 name: "bench.window" } }
        event_metadata { key: 2 value { id: 2 name: "hserve.poll" } } }
    ''')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    tr = PT.load_program(str(path))
    assert list(tr.devices) == ["/device:TPU:0"]
    idle = PT.reduce_program(tr)["idle_by_program_span"]
    assert idle == {"hserve.poll": pytest.approx(400e-9),
                    "none": pytest.approx(200e-9)}


def test_scope_own_time_sums_to_the_step_device_own_time():
    stages = {"jit_step(7)": {"fusion.1": "crt", "while.2": "ntt",
                              "fusion.3": "icrt"}}
    sc = PT.reduce_program(_nested(stages))["device_by_scope"]
    # the loop owns 250 less its two nested ops; copy.4 has no scope;
    # the slice program's fusion.1 is not the step's
    assert sc == {"crt": pytest.approx(100e-9), "ntt": pytest.approx(100e-9),
                  "modmul": 0.0, "icrt": pytest.approx(100e-9),
                  "other": pytest.approx(50e-9)}
    assert sum(sc.values()) == pytest.approx(350e-9)


def test_stage_of_takes_the_innermost_stage_scope():
    assert PT.stage_of("jit(step)/he.region2/he.icrt/vmap(icrt)/add") \
        == "icrt"
    assert PT.stage_of("jit(step)/he.region1/he.intt/while/body/mul") \
        == "ntt"
    assert PT.stage_of("jit(step)/he.region1/add") is None


def test_recorded_cpu_trace_with_program_spans_and_scopes(tmp_path):
    """A real CPU trace: hserve.* annotations nest in the bench ones,
    and the scopes of a jitted step label its ops through the HLO the
    trace holds; the stages add up to the step's own device time."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("he.crt"):
            y = jnp.sin(x) * 3.0
        with jax.named_scope("he.icrt"):
            return jnp.tanh(y @ x)

    x = jnp.ones((512, 512))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll"), \
                    jax.profiler.TraceAnnotation("hserve.poll"):
                with jax.profiler.TraceAnnotation("hserve.h2d", bytes=64):
                    pass
                with jax.profiler.TraceAnnotation("hserve.wait"):
                    step(x).block_until_ready()
                with jax.profiler.TraceAnnotation("hserve.retire"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    tr = PT.load_program(find_xplane(str(tmp_path)))
    red = PT.reduce_program(tr)
    assert red["program_spans"]["hserve.poll"]["n"] == 3
    assert red["h2d_bytes"] == 3 * 64
    assert red["idle_by_program_span"]["hserve.retire"] >= 0.05
    sc = red["device_by_scope"]
    assert sc["crt"] > 0 and sc["icrt"] > 0
    step_ops = [Event("", s, d) for ops in tr.devices.values()
                for s, d, _, m in ops if m.startswith("jit_step(")]
    window = next(e for e in tr.host if e.name == "bench.window")
    own: dict = {}
    PT._self_times(step_ops, window.start_ns, window.end_ns, own)
    assert sum(sc.values()) == pytest.approx(own[""] * 1e-9, rel=1e-9)


def _record(program, trace=True):
    return {"window_s": 10.0, "batch": 8, "batches": [],
            "serve": {"per_op": {"mul": {"batches": 20, "wall_s": 8.0}}},
            "latencies_s": [], "due_by_rid": {}, "lifecycle": None,
            "trace": {} if trace else None, "program": program}


NEW_READERS = {
    "engine.assemble_ms.sat": 10.0, "engine.h2d_ms.sat": 20.0,
    "engine.launch_ms.sat": 5.0, "engine.retire_ms.sat": 40.0,
    "engine.h2d_mb.sat": 318.767104, "pipeline.crt_ms.sat": 100.0,
    "pipeline.ntt_ms.sat": 300.0, "pipeline.modmul_ms.sat": 50.0,
    "pipeline.icrt_ms.sat": 200.0}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_new_readers_read_a_hand_built_record(name):
    spans = {"hserve.batch_assemble": {"s": 0.2, "n": 20},
             "hserve.h2d": {"s": 0.4, "n": 20},
             "hserve.launch": {"s": 0.1, "n": 20},
             "hserve.retire": {"s": 0.8, "n": 20}}
    scopes = {"crt": 2.0, "ntt": 6.0, "modmul": 1.0, "icrt": 4.0,
              "other": 3.0}
    read = R.metric_reader(name, ROOT)
    assert read(_record({"program_spans": spans, "device_by_scope": scopes,
                         "h2d_bytes": 20 * 318_767_104})) \
        == pytest.approx(NEW_READERS[name])
    # what a program without the spans, scopes or counter leaves
    assert read(_record({"program_spans": {}, "device_by_scope": None,
                         "h2d_bytes": 0})) is None
    assert read(_record(None)) is None
    # an untraced run: nothing to read, and no trace is looked for
    rec = _record(None, trace=False)
    del rec["program"]
    assert read(rec) is None and rec["program"] is None


def test_traced_tiny_cell_reports_the_new_metrics_on_cpu():
    """The whole traced run at a toy size: every new per-layer metric is
    read, and the bytes per batch are exactly the batch's operands."""
    cell = tiny_cell("mul_sat")
    res = R.run_cell(cell, 2**31 + 9, 2.0, True, require_tpu=False)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert set(NEW_READERS) <= set(got)
    conf = cell["config"]
    params = R.make_params(conf)
    words = params.qlimbs(params.logQ)
    assert got["engine.h2d_mb.sat"]["value"] == pytest.approx(
        4 * conf["batch"] * params.N * words * 4 / 1e6)
    for name in NEW_READERS:
        assert got[name]["value"] >= 0.0
    assert got["pipeline.icrt_ms.sat"]["value"] > 0.0
