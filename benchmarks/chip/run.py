#!/usr/bin/env python3
"""Chip benchmark of the DuDe trainer: runs one cell of ``BENCHMARK.json``
once and prints its result as the last line of standard output.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name.  A workload ``<config>.<traffic>`` reads
``configs/<config>.json`` (the sizes as run, the published source and the
cuts), ``traffic/<traffic>.json`` (the mix; its ``mode`` names
``modes/<mode>.py``), and ``limits/<workload>.json`` (the limits of the
check).  The configuration's ``family`` names its plain reference,
``configs/<family>.py``, and how the program is given it,
``configs/<family>_program.py``.  A per-layer metric ``<name>`` is read by
``metrics/<name>.py``.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
window under the JAX profiler and prints its per-layer metrics, the device's
busy and window seconds, and a breakdown of device time and idle gaps.
After the window the program's state is freed and the plain reference runs
over the checked steps' inputs; ``correct`` says whether every compared
number is within its limit, and the numbers and limits are printed last on
standard error and last in the result line.

Exits non-zero, with no result, when JAX finds no TPU, fewer chips than the
cell asks for, or a ``device_kind`` that ``peaks.json`` does not list.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def process_seconds() -> float:
    """Seconds since this process started (from /proc), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


_T_IMPORT = time.perf_counter()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write a cut of the compact trace here "
                         "(.json.gz)")
    return ap.parse_args(argv)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def find(bench: dict, key: str, name: str) -> dict:
    for e in bench[key]:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {key[:-1]} named {name!r} in "
                     "BENCHMARK.json")


def setup_jax():
    """Import JAX with its compile cache at the checkout's fixed path (and
    no TPU runtime logs under /tmp)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def check_devices(jax, chips: int, peaks_fn) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU; JAX found "
                         f"{devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    try:
        peaks_fn(devs[0].device_kind)
    except LookupError as e:
        raise SystemExit(f"run.py: {e}") from None
    return devs


def make_run(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, cfg: dict = None, mix: dict = None):
    """The cell's ``Run`` and mode.  ``cfg`` / ``mix`` stand in for the
    named files (tests run the harness at a small size)."""
    import harness
    import tracing
    import traffic as gen
    cell = find(bench, "workloads", workload)
    cfg = cfg or harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    mix = mix or harness.load_json(HERE / "traffic" /
                                   f"{cell['traffic']}.json")
    ref, prog = harness.load_family(cfg)
    cap = tracing.Capture(trace, str(ROOT / ".bench_trace" / workload))
    run = harness.Run(workload=workload, cfg=cfg, mix=mix, seed=seed,
                      seconds=seconds, ref=ref, prog=prog, capture=cap,
                      traffic=gen.make(mix, cfg["vocab_size"], seed),
                      keys=gen.seeds(seed))
    mod = harness.load_module(HERE / "modes" / f"{mix['mode']}.py",
                              f"mode_{mix['mode']}")
    return cell, run, mod.Mode(run)


class MetricInput:
    """What a metric reader sees (``metrics/<name>.py``: ``read(m)``)."""

    def __init__(self, kind, trace, win, cfg, mix, chips, peak):
        import counts
        self.kind, self.trace, self.win = kind, trace, win
        self.chips, self.peak = chips, peak
        self.flops_per_token = counts.train_flops_per_token(cfg,
                                                            mix["seq_len"])
        run = cfg["run"]
        self.round_bytes = counts.round_bytes(
            mix["n_workers"], counts.param_count(cfg),
            counts.dtype_bytes(run["grad_dtype"]),
            counts.dtype_bytes(run["buffer_dtype"]))


def per_layer(bench: dict, workload: str, m) -> dict:
    import harness
    out = {}
    for e in bench["per_layer"]:
        if workload not in e.get("workloads", [workload]):
            continue
        mod = harness.load_module(HERE / "metrics" / f"{e['name']}.py",
                                  f"metric_{e['name']}")
        v = mod.read(m)
        if v is not None:
            out[e["name"]] = {"value": float(v), "unit": e["unit"]}
    return out


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e30


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, keep_trace=None, devices=None, chips=1,
             peak=None, cfg=None, mix=None, hook=None, limits=None) -> dict:
    """One run of one cell; returns the result object.  ``hook(run,
    mode)``, if given, is called after the mode is built (tests plant
    faults there); ``limits`` stands in for the cell's limits file."""
    import jax
    import check
    import harness
    import tracing
    _, run, mode = make_run(bench, workload, seed, seconds, trace, cfg,
                            mix)
    if hook is not None:
        hook(run, mode)
    limits = limits or harness.load_json(HERE / "limits" /
                                         f"{workload}.json")
    mode.setup()
    setup_s = process_seconds()
    win = mode.window()
    used = (devices or jax.devices())[:chips]
    mem = [d.memory_stats() or {} for d in used]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in mem)
    log(f"[{workload}] memory_stats after the window: {mem[0]}")
    log(f"[{workload}] seed {seed}: set-up {setup_s:.2f} s, window "
        f"{win['seconds']:.3f} s, {win['attempted']} steps, peak "
        f"{peak_bytes} B")
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices or jax.devices()),
              "memory_peak_bytes": peak_bytes}
    names = {e["name"] for e in bench["end_to_end"]
             if workload in e.get("workloads", [workload])}
    result = {"correct": False, "attempted": win["attempted"],
              "failed": win["failed"]}
    breakdown = None
    if trace:
        compact = tracing.extract(run.capture.newest())
        if keep_trace:
            tracing.save_compact(compact, keep_trace, max_rows=4000)
        red = tracing.Reduced(compact)
        m = MetricInput(mode.kind, red, win, run.cfg, run.mix, chips, peak)
        metrics = per_layer(bench, workload, m)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.top_gaps(10)}
        log(f"[{workload}] trace: busy {red.busy_s:.4f} s of "
            f"{red.window_s:.4f} s; idle by host span "
            f"{red.idle_by_label()}")
    else:
        metrics = {}
        values = dict(win["metrics"], setup_s=setup_s,
                      peak_hbm_gb=peak_bytes / 1e9)
        for e in bench["end_to_end"]:
            if e["name"] in names:
                metrics[e["name"]] = {"value": float(values[e["name"]]),
                                      "unit": e["unit"]}
    mode.free()
    t0 = time.perf_counter()
    ref = mode.reference()
    g = check.gaps(mode.readings, ref)
    ok, rows = check.decide(g, limits)
    ok = ok and win["failed"] == 0
    log(f"[{workload}] reference {time.perf_counter() - t0:.2f} s; program "
        f"losses {mode.readings['losses']}; reference losses "
        f"{ref['losses']}; leaves left out {check.excluded(ref)}")
    result.update(correct=ok, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": _finite(v["value"]),
                            "limit": v["limit"]} for k, v in rows.items()}
    for k, v in rows.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench_path = ROOT / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())
    cell = find(bench, "workloads", args.workload)
    jax = setup_jax()
    import counts
    devs = check_devices(jax, cell["chips"], counts.peaks)
    peak = counts.peaks(devs[0].device_kind)
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), args.keep_trace, devs, cell["chips"],
                      peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
