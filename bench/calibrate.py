#!/usr/bin/env python3
"""Readings that the limits of the comparison are set from, on the chip.

    python3 bench/calibrate.py --workload mnist_mlp_k50.scan \\
        --program-seeds 101-112 --control-seeds 201-203 \\
        --fault-seeds 301-303 --out calib.json

In one process, with one compile of the cell's program:

* ``program``: for each seed, the first call of the timed path against
  the reference (the lower readings are the largest of these);
* ``control``: the reference one precision below the configuration's
  (``reference.control``), put in the program's place, against the
  reference;
* ``faults``: the reference with each planted fault of
  ``reference.FAULTS`` against the clean reference.

``--faults`` picks some of them.  ``--rehearse`` runs on the CPU at the
configuration's reduced size.  ``--config FILE --traffic MIX`` reads a
configuration that ``BENCHMARK.json`` does not name (``harness.config_cell``)
in place of ``--workload``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload")
    which.add_argument("--config", type=Path)
    ap.add_argument("--traffic", default="scan")
    ap.add_argument("--program-seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default=None,
                    help="comma-separated subset of reference.FAULTS")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from benchlib import harness
    harness.use_checkout_cache()

    import jax

    from benchlib import checks, gen, reference

    cell = (harness.config_cell(f"{args.config.stem}.{args.traffic}",
                                args.config, args.traffic)
            if args.config else harness.load_cell(args.workload))
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: found {dev.platform}", file=sys.stderr)
        return 2
    conf = harness.rehearsal_conf(cell.conf) if args.rehearse else cell.conf
    fl = conf["fl"]
    T = fl["rounds"]
    out = {"workload": cell.name, "device": dev.device_kind,
           "platform": dev.platform, "program": [], "control": [],
           "faults": {}, "seconds": {}}

    def save():
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))

    def say(msg):
        print(msg, flush=True)

    def ref_run(seed, **kw):
        """The reference for the trajectory of ``seed``, its clustering
        key from the configuration's ``plan_seed``."""
        plan_key = reference.program_keys(fl["plan_seed"], T)[0]
        _, init_key, rkeys = reference.program_keys(seed, T)
        t = time.perf_counter()
        r = reference.trajectory(cell.model, conf, inputs, plan_key,
                                 init_key, rkeys, **kw)
        return r, time.perf_counter() - t

    t = time.perf_counter()
    inputs = gen.make_inputs(conf)
    ex = None
    if args.program_seeds:
        ex = harness.executor_class(cell.traffic)(
            conf, cell.traffic, cell.model, inputs)
        say(f"compiled in {time.perf_counter() - t:.1f} s (trace_compile "
            f"{ex.timers.seconds.get('trace_compile', 0):.1f} s)")

    for s in args.program_seeds:
        pseed = harness.program_seed(s)
        t = time.perf_counter()
        ex.start(pseed)
        t_call = time.perf_counter() - t
        ref, t_ref = ref_run(pseed)
        numbers, note = checks.compare(ex.first, ref)
        out["program"].append({"seed": s, "program_seed": pseed,
                               "numbers": numbers,
                               "rounds": checks.per_round(ex.first, ref),
                               "first_call_s": t_call, "reference_s": t_ref})
        say(f"program seed {s}: {numbers} (call {t_call:.2f} s, "
            f"reference {t_ref:.2f} s) {note}")
        save()

    if ex is not None:
        ex.free()
        jax.clear_caches()

    clean = {}
    for s in args.control_seeds:
        pseed = harness.program_seed(s)
        clean[pseed], t_ref = ref_run(pseed)
        ctl, t_ctl = ref_run(pseed, **reference.control(conf))
        numbers, note = checks.compare(ctl, clean[pseed])
        out["control"].append({"seed": s, "numbers": numbers,
                               "rounds": checks.per_round(ctl, clean[pseed]),
                               "reference_s": t_ref, "control_s": t_ctl})
        say(f"control seed {s}: {numbers} (reference {t_ref:.2f} s, "
            f"control {t_ctl:.2f} s)")
        save()
    for fault in (args.faults.split(",") if args.faults
                  else reference.FAULTS):
        out["faults"][fault] = []
        for s in args.fault_seeds:
            pseed = harness.program_seed(s)
            if pseed not in clean:
                clean[pseed], _ = ref_run(pseed)
            bad, _ = ref_run(pseed, fault=fault)
            numbers, note = checks.compare(bad, clean[pseed])
            out["faults"][fault].append({
                "seed": s, "numbers": numbers,
                "rounds": checks.per_round(bad, clean[pseed])})
            say(f"fault {fault} seed {s}: {numbers}")
            save()
    out["seconds"]["total"] = time.perf_counter() - T_START
    save()
    say(f"done in {out['seconds']['total']:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
