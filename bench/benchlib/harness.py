"""One run of one cell: set-up, the measured window, the comparison with the
plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json   sizes, as run; limits of the comparison
    bench/configs/<config>.py     the program's model, the plain reference
                                  model, FLOPs per sample
    bench/traffic/<mix>.json      the mix: which executor the window
                                  drives, and its parameters
    bench/executors/<executor>.py ``Executor``: how the program is compiled
                                  and driven
    bench/metrics/<metric>.py     ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
PEAKS = Path(__file__).with_name("peaks.json")


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (a fixed path, part of every entry's key), with no cap on its size, so
    that a second run loads every program, the ~0.4-1.2 GB ones that
    embed a configuration's data included, and compiles nothing.  Also
    cache programs however quickly they compiled, and keep the TPU
    runtime's logs out of fixed paths.  Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    model: object
    traffic: dict
    end_to_end: list
    per_layer: list


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def config_cell(name: str, conf_path: Path, traffic: str,
                chips: int = 1) -> Cell:
    """The cell ``name``: the configuration at ``conf_path`` (its ``.json``,
    with its ``.py`` beside it) under the mix ``bench/traffic/<traffic>.json``,
    reporting the metrics that ``BENCHMARK.json`` asks of it.  The
    configuration need not be in ``BENCHMARK.json``: such a cell can be
    rehearsed through ``run_cell`` before it is a cell of the benchmark."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return Cell(
        name=name, chips=chips, conf=json.loads(conf_path.read_text()),
        model=load_module(conf_path.with_suffix(".py")),
        traffic=json.loads(
            (BENCH / "traffic" / f"{traffic}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def load_cell(workload: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"choose from {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return config_cell(workload, ROOT / config["file"], cell["traffic"],
                       int(cell["chips"]))


def rehearsal_conf(conf: dict) -> dict:
    """The configuration at the reduced size of its ``rehearsal`` entry:
    fewer clients, samples and rounds, every width as published."""
    conf = json.loads(json.dumps(conf))
    r = conf["rehearsal"]
    conf["topology"]["num_clients"] = r["num_clients"]
    conf["data"]["num_train"] = r["num_train"]
    conf["data"]["num_test"] = r["num_test"]
    conf["fl"]["eval_samples"] = r["num_test"]
    conf["fl"]["rounds"] = r["rounds"]
    return conf


def program_seed(seed: int) -> int:
    """The run's ``--seed`` (any whole number) as a program seed in
    [0, 2**30), so that seed blocks stay inside int32."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0]
               % 2**30)


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in {PEAKS.name};"
                         f" known: {sorted(table)}")
    return table[kind]


def sizes(conf: dict, model, inputs: dict) -> dict:
    """The round's shapes and its model FLOPs: local training (forward and
    backward of every minibatch), the eval's forward pass, and the sync's
    three matmuls (2CKd + 2C²d + 2KCd) counted once.  A sample is what
    ``model.sample_flops`` counts: an image, or a whole token sequence.

    ``d`` counts the params that ``model.reference_init`` returns: those
    that are trained and synced.  Weights that a configuration's apply
    closes over, drawn from a fixed key as the clustering is, are neither
    trained nor synced, and are not in ``d``."""
    import jax
    fl = conf["fl"]
    K, n_k = inputs["ys"].shape[:2]
    C = fl["num_clusters"]
    shapes = jax.eval_shape(model.reference_init, jax.random.PRNGKey(0))
    d = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    steps = max(fl["local_epochs"] * (n_k // fl["batch_size"]), 1)
    f = model.sample_flops()
    local = K * steps * fl["batch_size"] * f["train"]
    evals = fl["eval_samples"] * f["forward"]
    sync = 2 * C * K * d + 2 * C * C * d + 2 * K * C * d
    return {"K": K, "C": C, "d": d, "steps": steps, "local_flops": local,
            "eval_flops": evals, "sync_flops": sync,
            "round_flops": local + evals + sync}


def program_args(conf: dict, model, inputs: dict):
    """The entry point's arguments for a configuration: ``(args, cfg,
    topo_cfg)`` with ``args`` = (init, apply, loss, topology, client data,
    test data), as an executor passes them to ``run_rounds`` and its
    kin."""
    from repro.core.topology import Topology, TopologyConfig
    from repro.training import FLConfig
    t, fl = conf["topology"], conf["fl"]
    topo = Topology(positions=inputs["positions"],
                    link_gain=inputs["link_gain"],
                    link_snr=inputs["link_snr"],
                    adjacency=inputs["adjacency"],
                    noise_var=float(t["noise_var"]),
                    total_power=float(t["total_power"]))
    tcfg = TopologyConfig(**{f.name: t[f.name] for f in
                             dataclasses.fields(TopologyConfig)})
    init, apply, loss = model.program_model()
    cfg = FLConfig(strategy=fl["strategy"], rounds=fl["rounds"],
                   local_epochs=fl["local_epochs"],
                   batch_size=fl["batch_size"], lr=fl["lr"],
                   num_clusters=fl["num_clusters"], snr_db=fl["snr_db"],
                   eval_samples=fl["eval_samples"], seed=fl["plan_seed"])
    args = (init, apply, loss, topo, inputs["xs"], inputs["ys"],
            inputs["xte"], inputs["yte"])
    return args, cfg, tcfg


def matmul_precision(conf: dict):
    """The context in which the program is traced: the configuration's
    ``model.matmul_precision`` (``default`` leaves JAX's own, on the TPU
    one bfloat16 pass for a float32 matmul)."""
    import jax
    p = conf["model"]["matmul_precision"]
    return jax.default_matmul_precision(None if p == "default" else p)


def executor_class(traffic: dict):
    """The ``Executor`` of ``bench/executors/<traffic["executor"]>.py``:
    how the window compiles and drives the program.  It is built as
    ``Executor(conf, traffic, model, inputs)`` (compile and warm), then
    ``start(seed)`` (the first call, the one compared), ``call(i)`` for each
    call of the window, ``free()`` and ``check()``; it exposes
    ``rounds_per_call``, ``calls`` and the ``timers`` (``PhaseTimers``)."""
    return load_module(BENCH / "executors"
                       / f"{traffic['executor']}.py").Executor


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a per-layer metric's ``read(run)`` may look at."""
    sizes: dict
    timers: dict            # PhaseTimers seconds: trace_compile, execute
    trace: object           # tracing.Trace of the window, or None
    rounds: int             # trajectory-rounds finished in the window
    window_s: float
    chips: int
    peaks: dict | None


class _CompileCounter:
    def __init__(self):
        self.counts: dict[str, int] = {}

    def __call__(self, event: str, **kwargs) -> None:
        if event.startswith("/jax/compilation_cache/"):
            key = event.rsplit("/", 1)[-1]
            self.counts[key] = self.counts.get(key, 0) + 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def _device_record(jax, devices, chips: int) -> dict:
    peak = 0
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool, t_start: float) -> int:
    """One run of the ``BENCHMARK.json`` cell ``workload``; the process's
    exit code."""
    return run_cell(load_cell(workload), seed, seconds, trace, rehearse,
                    t_start)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float) -> int:
    """One run of ``cell`` (``load_cell`` or ``config_cell``); the
    process's exit code."""
    import jax

    counter = _CompileCounter()
    jax.monitoring.register_event_listener(counter)
    try:
        return _run(jax, counter, cell, seed, seconds, trace, rehearse,
                    t_start)
    finally:
        jax.monitoring.unregister_event_listener(counter)


def _run(jax, counter, cell: Cell, seed: int, seconds: float, trace: bool,
         rehearse: bool, t_start: float) -> int:
    workload = cell.name
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        log(f"no TPU: JAX found {len(devices)} {platform} device(s); this "
            f"benchmark measures the chip (--rehearse runs it on the CPU)")
        return 2
    if len(devices) < cell.chips:
        log(f"{workload} needs {cell.chips} chip(s); JAX found "
            f"{len(devices)}")
        return 2
    peaks = None if rehearse else peaks_for(devices[0].device_kind)
    conf = rehearsal_conf(cell.conf) if rehearse else cell.conf

    from benchlib import gen
    inputs = gen.make_inputs(conf)
    pseed = program_seed(seed)
    size = sizes(conf, cell.model, inputs)
    ex = executor_class(cell.traffic)(conf, cell.traffic, cell.model,
                                      inputs)
    ex.start(pseed)
    setup_s = time.perf_counter() - t_start
    log(f"{workload}: seed {seed} -> program seed {pseed}; K={size['K']} "
        f"C={size['C']} d={size['d']} steps/round={size['steps']}; "
        f"set-up {setup_s:.3f} s (compile "
        f"{ex.timers.seconds.get('trace_compile', 0.0):.3f} s); "
        f"compile cache {counter.snapshot()}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    before = counter.snapshot()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        i = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                ex.call(i)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.snapshot().get("compile_requests_use_cache", 0) - (
        before.get("compile_requests_use_cache", 0))
    rounds = i * ex.rounds_per_call
    device = _device_record(jax, devices, cell.chips)
    log(f"window: {i} calls, {rounds} rounds in {window_s:.6f} s; "
        f"{compiles} compile request(s) inside the window")

    tr = None
    if trace:
        from benchlib import tracing
        tr = tracing.load(trace_dir, host_ops=rehearse)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s

    timers = dict(ex.timers.seconds)
    ex.free()
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    numbers, failed, notes = ex.check()
    from benchlib import checks
    correct, record = checks.verdict(numbers, conf["check"]["limits"])
    attempted = ex.calls * ex.rounds_per_call // conf["fl"]["rounds"]
    log(f"reference comparison {time.perf_counter() - t_ref:.3f} s; "
        + "; ".join(notes))

    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed)}
    if trace:
        info = Run(sizes=size, timers=timers, trace=tr,
                   rounds=rounds, window_s=tr.window_s, chips=cell.chips,
                   peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    else:
        values = {"rounds_per_s": rounds / window_s, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = record
    for name, r in record.items():
        log(f"check {name} {r['value']!r} limit {r['limit']!r}")
    if rehearse:
        log(f"rehearsal result on {platform} (not a chip result): "
            f"{json.dumps(result)}")
        print(f"rehearsal on {platform}: no chip result", flush=True)
        return 0 if correct else 1
    print(json.dumps(result), flush=True)
    return 0
