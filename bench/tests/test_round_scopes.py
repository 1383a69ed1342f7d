"""The per-layer metrics that read the round's named scopes
(``round.*_ms``, ``round.unscoped_share``) and the ``prepare`` phase
(``setup.prepare_s``), on a hand-written trace and scope map."""
import pytest
from jax.profiler import ProfileData

from benchlib import harness, tracing

# One TPU op line, times in µs: a local-step fusion at [0, 4) with a nested
# unscoped copy at [1, 2); the minibatch gather at [4, 6); the sync kernel
# at [6, 6.5) and a sync fusion at [6.5, 7); the eval's matmul at [7, 8);
# an unscoped loop op at [8, 9).  The window is [0, 10) µs.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 6500000 duration_ps: 500000 }
    events { metadata_id: 6 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 8000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[64,28,28]{2,1,0} fusion(f32[1200,28,28]{2,1,0} %x)" } }
  event_metadata { key: 4 value { id: 4 name: "%cwfl_round.8 = (f32[50,184320]{1,0}) custom-call(f32[3,50]{1,0} %a)" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.9 = f32[3,184320]{1,0} fusion(f32[3,184320]{1,0} %n)" } }
  event_metadata { key: 6 value { id: 6 name: "%dot.4 = f32[10000,200]{1,0} dot(f32[10000,784]{1,0} %x, f32[784,200]{1,0} %w)" } }
  event_metadata { key: 7 value { id: 7 name: "%add.5 = s32[] add(s32[] %i, s32[] %one)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""

SCOPES = {"fusion.1": "fl_local", "fusion.3": "fl_batch",
          "cwfl_round.8": "fl_sync", "fusion.9": "fl_sync",
          "dot.4": "fl_eval"}
ROUNDS = 2
NAMES = ("round.local_ms", "round.batch_ms", "round.sync_ms",
         "round.eval_ms", "round.unscoped_share", "setup.prepare_s")


def _run(trace, timers=None):
    return harness.Run(sizes={"K": 50, "C": 3, "d": 184214,
                              "round_flops": 1e9},
                       timers=timers or {"prepare": 2.5}, trace=trace,
                       rounds=ROUNDS, window_s=10e-6, chips=1,
                       peaks=harness.peaks_for("TPU v5 lite"))


def _read(name, run):
    return harness.load_module(harness.BENCH / "metrics"
                               / f"{name}.py").read(run)


@pytest.fixture
def trace():
    return tracing.reduce(ProfileData.from_text_proto(TRACE))


@pytest.fixture
def scoped(monkeypatch):
    from repro.obs import profiling
    monkeypatch.setattr(profiling, "op_scopes", lambda: dict(SCOPES))


def test_round_metrics_read_their_scopes(trace, scoped):
    run = _run(trace)
    # ms per round: self µs ÷ 2 rounds ÷ 1000.
    assert _read("round.local_ms", run) == pytest.approx(3e-3 / ROUNDS)
    assert _read("round.batch_ms", run) == pytest.approx(2e-3 / ROUNDS)
    assert _read("round.sync_ms", run) == pytest.approx(1e-3 / ROUNDS)
    assert _read("round.eval_ms", run) == pytest.approx(1e-3 / ROUNDS)
    # The nested copy and the loop op: 2 of 9 busy µs.
    assert _read("round.unscoped_share", run) == pytest.approx(100 * 2 / 9)
    assert _read("setup.prepare_s", run) == 2.5
    # The kernel's own metric sees only the kernel, the sync's scope more.
    assert _read("round.sync_ms", run) >= _read("sync_kernel_ms", run)


def test_scopes_and_unscoped_share_add_up_to_busy_time(trace, scoped):
    run = _run(trace)
    busy = trace.busy_s("/device:TPU:0")
    scoped_s = sum(_read(n, run) for n in NAMES[:4]) * ROUNDS * 1e-3
    unscoped_s = _read("round.unscoped_share", run) / 100 * busy
    self_s = sum(s for _, s in tracing.self_times(
        trace.devices["/device:TPU:0"]))
    assert scoped_s + unscoped_s == pytest.approx(self_s)
    assert self_s == pytest.approx(busy)


def test_no_scope_map_reads_nothing(trace, monkeypatch):
    from repro.obs import profiling
    monkeypatch.setattr(profiling, "op_scopes", lambda: {})
    for name in NAMES[:5]:
        assert _read(name, _run(trace)) is None


def test_program_without_scopes_reads_nothing(trace, monkeypatch):
    # A program that predates the scopes has no ``op_scopes`` to import.
    from repro.obs import profiling
    monkeypatch.delattr(profiling, "op_scopes")
    for name in NAMES[:5]:
        assert _read(name, _run(trace)) is None


def test_no_trace_or_no_phase_reads_nothing(scoped):
    for name in NAMES[:5]:
        assert _read(name, _run(None)) is None
    assert _read("setup.prepare_s",
                 _run(None, timers={"trace_compile": 1.0})) is None
