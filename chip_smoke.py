#!/usr/bin/env python3
"""Bring-up smoke of the DuDe trainer on a TPU — not a benchmark.

Drives the two training paths through the ``repro.api`` entry points at
qwen2-0.5b's published widths (d_model 896, 14 query / 2 KV heads of 64,
d_ff 4864, vocab 151936, bf16, remat and layer scan on), cut in depth to fit
one v5e chip, with random weights from a seed:

  (a) ``Trainer.step`` for 4 rounds with ``server_backend="pallas"``: the
      fused round kernel, compiled (the step's HLO must hold
      ``tpu_custom_call``);
  (b) the same 4 rounds with ``"reference"``, same seed and batches, after
      (a)'s state is copied to the host and deleted: every step's loss and
      the parameters after 4 steps must be equal, and the parameters must
      have moved;
  (c) 8 arrivals through ``Trainer.run_async`` with ``FixedArrivals``;
  (d) the three fused kernels (dense, int8, sparse top-k) at n = 16
      workers, i.e. two 8-row groups per grid column on the TPU, against
      the reference backend through ``DuDeEngine.round_apply``.

``--chips 4`` runs only the P-sharded round step (mesh 1x4, mesh-native
engine, TP-native params feed, pallas) and compares it with the same config
unsharded on device 0.

Any failed check exits non-zero; so does a run that finds no TPU.  The last
line of standard output is one JSON object naming the device.

Usage: python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Trainer, TrainerConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import make_round_schedule, truncated_normal_speeds  # noqa: E402
from repro.core.engine import DuDeEngine  # noqa: E402
from repro.core.flatten import make_flat_spec  # noqa: E402
from repro.kernels.dude_update import kernel_grid  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.sampling import make_worker_sample_fn  # noqa: E402
from repro.optim import adamw, flat_twin, sgd  # noqa: E402
from repro.runtime import FixedArrivals  # noqa: E402

SEED = 0
STEPS = 4
ARRIVALS = 8
SEQ_LEN = 1024
N_WORKERS = 4
# Depth cut: the v5e compile of the round step at these widths needs
# 12.5 GB (pallas) / 11.1 GB (reference) at 8 layers; 10 and 12 layers
# push the reference step to 14.6 / 15.5 GB.
LAYERS = 8
# The arrival path holds up to n + 1 flat f32 [P] param versions (the
# workers' stale snapshots) besides the slabs: about 58 bytes per parameter
# at its peak, 11.4 GB at 4 layers (P = 196M).
ASYNC_LAYERS = 4
CE_CHUNK = 128      # LM head + CE in 128-token chunks: no [T, V] logits
# |params_pallas - params_reference| after STEPS rounds: n = 4 is one row
# group, and both backends sum its rows in the same order; two chip runs
# read 0.0.
PARAM_TOL = 0.0
# the parameters must move at least this far in STEPS rounds, or the
# comparison above sees no kernel at work: far above the f32 spacing of the
# initial weights (under 1e-8 below |w| = 0.1)
MIN_MOVE = 1e-6
# the sharded forward reduces its tensor-parallel matmuls in another order,
# so its bf16 gradients differ in the last bits: the losses agree to
# SHARDED_LOSS_RTOL and the parameters to SHARDED_PARAM_RTOL of their move
SHARDED_LOSS_RTOL = 1e-2
SHARDED_PARAM_RTOL = 0.1

# Phase (d): 16 workers are two 8-row groups per grid column on the TPU.
# P spans several lane tiles and a ragged last one.
GROUP_N = 16
GROUP_P = (1 << 21) + 5 * 128
GROUP_ROUNDS = 3
GROUP_LR = 0.1
# (commit format, dense slab dtype, optimizer): one case per fused kernel
GROUP_CASES = (("f32", jnp.bfloat16, "adamw"),
               ("int8_ef", jnp.float32, "sgd"),
               ("topk_ef", jnp.float32, "sgd"))
# |pallas - reference| of g_bar and params after GROUP_ROUNDS: the
# gradients are multiples of 1/8, so the dense worker sum is exact in any
# order; the int8 slabs decode to inexact products and the two row groups
# sum them in another order than the reference.  Params move by ~0.1.
GROUP_TOL = 1e-5


def model_config(layers: int, base=None):
    """qwen2-0.5b at every published width, cut in depth and worker count."""
    base = base or get_config("qwen2_0_5b")
    return dataclasses.replace(base, num_layers=layers, n_workers=N_WORKERS,
                               ce_chunk=CE_CHUNK)


def trainer_config(arch, backend: str, mesh=None) -> TrainerConfig:
    # the bf16 model's gradients are bf16 already: raveling them as bf16
    # changes no value and saves the 4nP-byte f32 slab
    return TrainerConfig(arch=arch, server_backend=backend, mesh=mesh,
                         grad_dtype=jnp.bfloat16, seed=SEED)


def round_inputs(arch, steps: int, seq_len: int) -> list:
    """``steps`` host-side ``(batch, start_mask, commit_mask)`` rounds from
    the repo's batch pipeline and the paper's fixed-speed schedule."""
    n = arch.n_workers
    sample_fn = make_worker_sample_fn(arch, seq_len=seq_len,
                                      per_worker_batch=1, seed=SEED)
    sch = make_round_schedule(
        truncated_normal_speeds(n, std=1.0, seed=SEED + 1), steps)
    rng = np.random.default_rng(SEED)
    out = []
    for r in range(steps):
        per = [jax.tree.map(np.asarray, sample_fn(i, rng)) for i in range(n)]
        batch = jax.tree.map(lambda *xs: np.stack(xs), *per)
        out.append((batch, sch.start[r], sch.commit[r]))
    return out


def _free(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        leaf.delete()
    gc.collect()


def run_rounds(config: TrainerConfig, inputs: list) -> dict:
    """Phase (a)/(b): compile the round step, take ``len(inputs)`` steps
    through ``Trainer.step``, copy the final master params to the host and
    delete the device state."""
    trainer = Trainer.create(config)
    eng = trainer.engine
    t0 = time.perf_counter()
    compiled = jax.jit(trainer.step_fn, donate_argnums=(0,)).lower(
        trainer.state, *inputs[0]).compile()
    compile_s = time.perf_counter() - t0
    # the unpadded prefix: a P-sharded spec pads to another multiple
    size = eng.spec.size
    params0 = np.asarray(trainer.state.params)[:size]
    losses, step_s = [], []
    for batch, sm, cm in inputs:
        t0 = time.perf_counter()
        metrics = trainer.step(batch, sm, cm)
        jax.block_until_ready((trainer.state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    params = np.asarray(trainer.state.params)[:size]
    _free(trainer.state)
    trainer.state = None
    return {
        "P": eng.P, "tile": eng.tile,
        "grid": kernel_grid(eng.shard_P, eng.n_workers, eng.tile,
                            jax.default_backend() != "tpu"),
        "kernel_in_hlo": "tpu_custom_call" in compiled.as_text(),
        "compile_s": compile_s, "step_s": step_s, "losses": losses,
        "params": params, "moved": float(np.max(np.abs(params - params0))),
    }


def run_arrivals(config: TrainerConfig, n_arrivals: int,
                 seq_len: int) -> dict:
    """Phase (c): ``n_arrivals`` per-arrival server iterations through
    ``Trainer.run_async`` on the paper's fixed-speed arrival process."""
    trainer = Trainer.create(config)
    n = trainer.cfg.n_workers
    sample_fn = make_worker_sample_fn(trainer.cfg, seq_len=seq_len,
                                      per_worker_batch=1, seed=SEED)
    arrivals = FixedArrivals.from_speeds(
        truncated_normal_speeds(n, std=1.0, seed=SEED + 1))
    t0 = time.perf_counter()
    res = trainer.run_async(arrivals, n_arrivals, sample_fn, record_every=1)
    jax.block_until_ready(res.state)
    wall_s = time.perf_counter() - t0
    finite = bool(np.isfinite(np.asarray(res.state.params)).all())
    out = {"iters": int(res.stats.iters), "tau_max": int(res.tau_max),
           "losses": [float(x) for x in res.losses], "wall_s": wall_s,
           "params_finite": finite}
    _free(res.state)
    trainer.state = None
    return out


def run_row_groups(n: int, P: int, rounds: int, fmt: str, buffer_dtype,
                   opt_name: str) -> dict:
    """Phase (d): ``rounds`` rounds of ``DuDeEngine.round_apply`` at ``n``
    workers on a flat ``[P]`` vector, pallas against reference, from the
    same gradients (multiples of 1/8) and masks.  Round 0 latches every
    worker, round 1 commits every worker, later rounds draw both masks."""
    spec = make_flat_spec({"w": jax.ShapeDtypeStruct((P,), jnp.float32)})
    P = spec.padded_size
    opt = flat_twin(sgd(GROUP_LR) if opt_name == "sgd" else adamw(GROUP_LR))
    rng = np.random.default_rng(SEED)
    w0 = rng.normal(size=P).astype(np.float32)
    ins = []
    for r in range(rounds):
        fresh = rng.integers(-8, 9, size=(n, P)).astype(np.float32) / 8
        sm = np.ones(n, bool) if r == 0 else rng.random(n) < 0.5
        cm = np.full(n, r == 1) if r < 2 else rng.random(n) < 0.5
        ins.append((fresh, sm, cm))
    out = {}
    for backend in ("pallas", "reference"):
        eng = DuDeEngine(spec=spec, n_workers=n, backend=backend,
                         buffer_dtype=buffer_dtype, commit_format=fmt,
                         sparse_meta=fmt == "topk_ef")
        st, w = eng.init(), jnp.asarray(w0)
        o = opt.init(w)
        step = jax.jit(lambda st, f, a, b, w, o, eng=eng:
                       eng.round_apply(st, f, a, b, w, o, opt)).lower(
            st, *ins[0], w, o).compile()
        for fresh, sm, cm in ins:
            st, g_bar, w, o = step(st, jnp.asarray(fresh), sm, cm, w, o)
        out[backend] = (np.asarray(g_bar), np.asarray(w))
        if backend == "pallas":
            grid = kernel_grid(P, n, eng.tile, eng._interpret())
            in_hlo = "tpu_custom_call" in step.as_text()
        del st, o
    (gp, wp), (gr, wr) = out["pallas"], out["reference"]
    return {"P": P, "grid": grid, "kernel_in_hlo": in_hlo,
            "g_bar_diff": float(np.max(np.abs(gp - gr))),
            "params_diff": float(np.max(np.abs(wp - wr))),
            "moved": float(np.max(np.abs(wr - w0)))}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def peak_bytes(dev) -> int:
    """The device's peak allocation so far; 0 where the platform keeps no
    allocator statistics (the CPU)."""
    stats = dev.memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else 0


def report_rounds(tag: str, r: dict) -> None:
    print(f"[{tag}] P={r['P']:,} tile={r['tile']} grid={r['grid']} "
          f"tpu_custom_call={r['kernel_in_hlo']} "
          f"compile_s={r['compile_s']:.1f}")
    print(f"[{tag}] step host seconds after block_until_ready (bring-up, "
          f"not a benchmark): {[round(s, 3) for s in r['step_s']]}")
    print(f"[{tag}] losses={r['losses']}")


def describe(arch) -> str:
    return (f"{arch.name}: d_model={arch.d_model} heads={arch.num_heads}/"
            f"{arch.num_kv_heads}x{arch.hd} d_ff={arch.d_ff} "
            f"vocab={arch.vocab_size} dtype={jnp.dtype(arch.dtype).name} "
            f"remat={arch.remat} scan_layers={arch.scan_layers}; cut: "
            f"layers {arch.num_layers} of 24, n_workers {arch.n_workers} "
            f"of 16, seq_len {SEQ_LEN}, per-worker batch 1, ce_chunk "
            f"{arch.ce_chunk}, grad_dtype bf16, optimizer sgd lr 0.01")


def one_chip(dev) -> None:
    arch = model_config(LAYERS)
    print(f"[config] {describe(arch)}")
    inputs = round_inputs(arch, STEPS, SEQ_LEN)

    a = run_rounds(trainer_config(arch, "pallas"), inputs)
    report_rounds("a pallas", a)
    print(f"[a pallas] peak_bytes_in_use={peak_bytes(dev):,}")
    check(a["kernel_in_hlo"], "pallas step has no tpu_custom_call")
    check(np.isfinite(a["losses"]).all() and np.isfinite(a["params"]).all(),
          "pallas step is not finite")

    b = run_rounds(trainer_config(arch, "reference"), inputs)
    report_rounds("b reference", b)
    print(f"[b reference] peak_bytes_in_use={peak_bytes(dev):,}")
    diff = float(np.max(np.abs(a["params"] - b["params"])))
    print(f"[b reference] first-step loss pallas={a['losses'][0]!r} "
          f"reference={b['losses'][0]!r}; max|params diff| after {STEPS} "
          f"steps={diff!r} (tolerance {PARAM_TOL})")
    print(f"[b reference] per-step losses equal: "
          f"{a['losses'] == b['losses']}; max|params moved| after {STEPS} "
          f"steps: pallas={a['moved']!r} reference={b['moved']!r} (at least "
          f"{MIN_MOVE})")
    check(a["losses"][0] == b["losses"][0], "first-step losses differ")
    check(a["losses"] == b["losses"], "per-step losses differ")
    check(diff <= PARAM_TOL, f"params differ by {diff} > {PARAM_TOL}")
    check(min(a["moved"], b["moved"]) >= MIN_MOVE,
          f"params moved by less than {MIN_MOVE}")
    del a, b

    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"[c async] before: bytes_in_use="
          f"{int(dev.memory_stats()['bytes_in_use']):,} live_arrays={live:,}")
    arch_c = model_config(ASYNC_LAYERS)
    print(f"[c async] depth {ASYNC_LAYERS} layers, {ARRIVALS} arrivals, "
          f"FixedArrivals, same widths")
    c = run_arrivals(trainer_config(arch_c, "pallas"), ARRIVALS, SEQ_LEN)
    print(f"[c async] iters={c['iters']} tau_max={c['tau_max']} "
          f"wall_s={c['wall_s']:.1f} (bring-up, not a benchmark) "
          f"loss_ema={c['losses']}")
    print(f"[c async] peak_bytes_in_use={peak_bytes(dev):,}")
    check(c["iters"] == ARRIVALS, f"{c['iters']} of {ARRIVALS} arrivals")
    check(c["params_finite"] and np.isfinite(c["losses"]).all(),
          "async run is not finite")

    print(f"[d groups] n={GROUP_N} workers, {GROUP_ROUNDS} rounds, lr "
          f"{GROUP_LR}, tolerance {GROUP_TOL}")
    for fmt, dtype, opt_name in GROUP_CASES:
        d = run_row_groups(GROUP_N, GROUP_P, GROUP_ROUNDS, fmt, dtype,
                           opt_name)
        slab = jnp.dtype(dtype).name if fmt == "f32" else "int8"
        tag = f"{fmt} {slab} slabs, {opt_name}"
        print(f"[d groups] {tag}: P={d['P']:,} grid={d['grid']} "
              f"tpu_custom_call={d['kernel_in_hlo']} max|g_bar diff|="
              f"{d['g_bar_diff']!r} max|params diff|={d['params_diff']!r} "
              f"max|params moved|={d['moved']!r}")
        check(d["kernel_in_hlo"], f"{tag}: no tpu_custom_call")
        check(d["grid"][1] >= 2, f"{tag}: one row group only")
        check(max(d["g_bar_diff"], d["params_diff"]) <= GROUP_TOL,
              f"{tag}: pallas and reference differ")
        check(d["moved"] >= 1e3 * GROUP_TOL, f"{tag}: params did not move")


def compare_sharded(arch, seq_len: int, devs) -> tuple:
    """The P-sharded round step (1x4 mesh, mesh-native engine, TP-native
    params feed, pallas) and the same config unsharded on device 0:
    ``(sharded, unsharded, loss rel. diff, max|params diff|)``."""
    inputs = round_inputs(arch, STEPS, seq_len)
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    # the TP-native feed: the replicated one's GSPMD reshard of the raveled
    # gradients into the P-shards compiled 3x slower at this vocabulary
    sharded = dataclasses.replace(
        trainer_config(arch, "pallas", mesh=mesh), params_layout="tp")
    s = run_rounds(sharded, inputs)
    s["peaks"] = [peak_bytes(d) for d in devs[:4]]
    u = run_rounds(trainer_config(arch, "pallas"), inputs)
    diff = float(np.max(np.abs(s["params"] - u["params"])))
    rel = abs(s["losses"][0] - u["losses"][0]) / abs(u["losses"][0])
    return s, u, rel, diff


def four_chips(devs) -> None:
    arch = model_config(LAYERS)
    print(f"[config] {describe(arch)}")
    s, u, rel, diff = compare_sharded(arch, SEQ_LEN, devs)
    report_rounds("sharded 1x4", s)
    print(f"[sharded 1x4] per-device peak_bytes_in_use={s['peaks']}")
    report_rounds("unsharded dev0", u)
    print(f"[compare] first-step loss sharded={s['losses'][0]!r} "
          f"unsharded={u['losses'][0]!r} (rel {rel:.3g}, tolerance "
          f"{SHARDED_LOSS_RTOL}); max|params diff|={diff!r}, max|params "
          f"moved|={u['moved']!r} (tolerance {SHARDED_PARAM_RTOL} of it)")
    check(s["kernel_in_hlo"], "sharded step has no tpu_custom_call")
    check(rel <= SHARDED_LOSS_RTOL, "sharded first-step loss differs")
    check(u["moved"] >= MIN_MOVE, f"params moved by less than {MIN_MOVE}")
    check(diff <= SHARDED_PARAM_RTOL * u["moved"],
          f"sharded params differ by {diff}")
    check(min(s["peaks"]) > 0.1 * max(s["peaks"]),
          "slabs not spread over 4 devices")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    check(len(devs) >= args.chips, f"needs {args.chips} chips, found "
          f"{len(devs)}")
    print(f"[device] {dev.device_kind} x{len(devs)}")
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
