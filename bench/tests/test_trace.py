"""The reduction from a profiler trace to busy time, idle share and
kernel time (``benchlib.tracing``)."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from benchlib import harness, tracing

# A device plane as a TPU writes it, ops named by their HLO text (times in
# ps from the line's start):
# a fusion at [0, 2) µs with a nested copy at [0.5, 1) µs, the sync kernel
# at [3, 4) µs, and an op that runs past the window's end at [9, 12) µs.
# The host's bench.window span covers [0, 10) µs.
TPU_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 3000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%cwfl_round.8 = (f32[50,184320]{1,0}) custom-call(f32[3,50]{1,0} %a)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %cwfl_round.8)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_scan" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
}
"""


@pytest.fixture(scope="module")
def tpu_trace():
    return tracing.reduce(ProfileData.from_text_proto(TPU_TRACE))


def test_window_and_busy_union(tpu_trace):
    assert tpu_trace.window_s == pytest.approx(10e-6)
    # [0,2) ∪ [0.5,1) ∪ [3,4) ∪ [9,10) after clipping: 4 µs; the module
    # line is not an op line and is not counted.
    assert tpu_trace.busy_s("/device:TPU:0") == pytest.approx(4e-6)
    assert tpu_trace.mean_busy_s() == pytest.approx(4e-6)


def test_self_time_and_kernel_events(tpu_trace):
    top = {tracing.op_name(k): v for k, v in tpu_trace.top_ops(10)}
    assert top["fusion.1"] == pytest.approx(1.5e-6 + 1e-6)
    assert top["copy.2"] == pytest.approx(0.5e-6)
    assert tpu_trace.top_ops(1)[0][0].startswith("%fusion.1 = f32[8]")
    # copy.2 reads the kernel's output: only the kernel's own event counts.
    kernel = harness.load_module(harness.BENCH / "metrics"
                                 / "sync_kernel_ms.py")
    assert tpu_trace.op_count(kernel.is_kernel) == 1
    assert tpu_trace.op_seconds(kernel.is_kernel) == pytest.approx(1e-6)


def test_idle_gaps_named_by_host_span(tpu_trace):
    gaps = tpu_trace.idle_gaps(10)
    assert [round(s * 1e9) for _, s in gaps] == [5000, 1000]
    # [4, 9) µs: its midpoint lies in bench.wait inside bench.call.
    assert gaps[0][0] == "bench.wait"
    assert gaps[1][0] == "host:other"


def test_metrics_from_tpu_trace(tpu_trace):
    run = harness.Run(sizes={"K": 50, "C": 3, "d": 184214,
                                      "round_flops": 1e9},
                      timers={"trace_compile": 1.5}, trace=tpu_trace,
                      rounds=2, window_s=tpu_trace.window_s, chips=1,
                      peaks=harness.peaks_for("TPU v5 lite"))
    read = lambda name: harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)
    assert read("device_idle_share") == pytest.approx(60.0)
    assert read("sync_kernel_ms") == pytest.approx(0.5e-3)
    assert read("setup.compile_s") == 1.5
    assert read("mfu") == pytest.approx(100 * 2e9 / (10e-6 * 197e12))
    # 4·d·(2K+2C+1) bytes at 819 GB/s, twice, over 1 µs of kernel time.
    floor = 4 * 184214 * 107 / 819e9
    assert read("sync_kernel_roofline") == pytest.approx(100 * 2 * floor
                                                         / 1e-6)


def test_reduce_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        tracing.load(str(tmp_path))
    tr = tracing.load(str(tmp_path), host_ops=True)
    assert list(tr.devices) == ["/host:CPU"]
    assert 0 < tr.mean_busy_s() <= tr.window_s
    assert any("dot" in name for name, _ in tr.top_ops(20))
    for _, s, e in tr.devices["/host:CPU"]:
        assert tr.window[0] <= s < e <= tr.window[1]


def test_reduce_needs_one_window():
    text = TPU_TRACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce(ProfileData.from_text_proto(text))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="no peaks"):
        harness.peaks_for("TPU v99")
