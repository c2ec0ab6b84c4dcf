"""Kernel micro-benchmarks.

On CPU the Pallas kernels run in interpret mode, so wall-times are NOT
hardware-representative; the ``derived`` column therefore reports the
ANALYTIC HBM-traffic ratio (XLA path bytes / kernel path bytes) — the
quantity that determines the TPU speedup for these memory-bound ops —
plus interpret-mode allclose max-error vs. the oracle as a correctness pulse.

``--backend {reference,indexed,pallas,all}`` additionally sweeps the
ServerEngine round over the selected backends on IDENTICAL inputs at several
(n, P) points — unsharded, and (whenever more than one device is visible,
e.g. under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) P-axis
sharded over all devices — reporting per-backend round latency and the max
|g_bar| error vs. the reference backend, so the fusion win is measured, not
asserted.

The fused round+apply path (flat-state training) is swept separately:
backend x optimizer (sgd/momentum/adamw) on identical inputs, unsharded and
— with >1 device — P-axis sharded, with max |params| error vs. the
reference-backend flat apply as the correctness pulse.

The session-dispatch sweep times ``api.Trainer.step`` (the one-object
session facade) against the raw prejitted flat step on the identical state
and batch: ``derived`` is facade time / raw time, proving the facade adds
no per-step overhead beyond Python dispatch noise.

The arrival-throughput sweep (async runtime, docs/async.md) times one
server iteration of the per-arrival hot path — ``engine.commit`` + flat
optimizer apply, the AsyncRunner's jitted step — against the masked-step
baseline that expresses the same single arrival as a full ``round_apply``
with one-hot masks (streaming all ``[n, P]`` slabs for one worker's
commit).  Rows report arrivals/sec; ``derived`` is the runner-step
throughput over the masked baseline's.  A full-loop row measures the
``AsyncRunner`` end to end (host event loop + DeviceQueue included) on a
toy gradient.

The unravel sweep (TP-native param feed, docs/engine.md) compares the two
``params_layout`` paths on a real architecture's param shardings over a
(data, model) host mesh: ``replicated`` all-gathers the flat ``[P]`` master
vector onto every device before slicing leaves out, ``tp`` runs the
ppermute ring exchange that feeds each leaf straight from the P-shards.
Rows report measured call time plus the plan's analytic per-device peak
live bytes, ring/gather bytes moved, and the max per-leaf gather bound;
``derived`` for the tp rows is the footprint ratio (replicated full-vector
bytes / tp peak bytes).  Correctness pulse: max error vs. the eager
(placement-free) oracle — 0.0 = bit-for-bit.

The commit-format sweep (compressed slabs, docs/engine.md) prices the
``commit_format`` choices — f32 / int8_ef / topk_ef — on the per-arrival
hot path at several (n, P) points: analytic wire bytes per commit and
resident ``[n, P]`` slab bytes (the HBM win), measured arrivals/sec (the
quantize/dequantize cost), and the max |g_bar| error vs. the f32 engine
checked against the tile-wise quantization bound.

The sparse-transport sweep (docs/engine.md "Sparse commit transport")
prices the ``topk_ef`` SparseRow wire format against the dense topk_ef row
on structurally sparse gradients (a fixed number of touched 128-lane
tiles): actual wire bytes per commit (O(k * tiles_touched) vs O(P)),
measured server-side fold and worker-side encode throughput, and a bitwise
|g_bar| pulse — the sparse scatter-fold must equal the dense commit
bit-for-bit.

The transport sweep (docs/async.md "Multi-host transport") prices the
framed wire hop itself: the same 2-link hosted run (HostRunner + two
run_worker client threads, full protocol incl. handshake/snapshots/
heartbeats) over in-proc queues vs real loopback sockets — arrivals/sec
and framed byte totals each way; the in-proc row is the protocol-only
ceiling, the delta is the OS socket cost.

``--json-out`` (default ``benchmarks/BENCH_9.json``) writes every row as
machine-readable JSON — backend x (n, P) x sharded/unsharded, the
round+apply grid, the session-dispatch rows, the arrival-throughput rows,
the commit-format rows, the sparse-transport rows, the transport rows,
and the unravel rows — so the perf trajectory is tracked across PRs.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import BACKENDS, DuDeEngine
from repro.core.flatten import make_flat_spec
from repro.kernels import ref
from repro.kernels.ops import dude_update, flash_attention, flash_decode
from repro.launch.mesh import make_mesh
from repro.optim import flat_adamw, flat_momentum_sgd, flat_sgd
from repro.sharding import flat_train_state_shardings

F32 = 4

ENGINE_POINTS = ((8, 1 << 12), (16, 1 << 14), (64, 1 << 16))

FLAT_OPTS = {
    "sgd": flat_sgd(0.05),
    "momentum": flat_momentum_sgd(0.05),
    "adamw": flat_adamw(0.01, weight_decay=0.01),
}


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def engine_sweep(backends=BACKENDS, points=ENGINE_POINTS,
                 commit_frac: float = 0.25, sharded: bool = False) -> list[dict]:
    """Time one ServerEngine round per backend on identical random inputs.

    ``derived`` reports the ANALYTIC HBM-traffic ratio of each backend's
    round vs. the reference masked sweep (~9 unfused passes over the five
    streams, per the seed's estimate): reference is the baseline (1.0), the
    fused pallas kernel does one read + one write per stream (2 passes =>
    4.5x), and the indexed backend — given the static active-set bound
    ``index_width = k`` the benchmark wires in, matching the Bernoulli mask
    density — touches only ~(4k+2)P elements twice.

    ``sharded=True`` runs the same rounds mesh-native: EngineState P-axis
    sharded over ALL visible devices, shard_map round (requires >1 device).
    """
    mesh = None
    ndev = 1
    if sharded:
        ndev = jax.device_count()
        if ndev < 2:
            raise ValueError("sharded sweep needs >1 device "
                             "(set --xla_force_host_platform_device_count)")
        mesh = make_mesh((ndev,), ("p",))
    rows = []
    key = jax.random.PRNGKey(42)
    for n, P in points:
        spec = make_flat_spec(jnp.zeros((P,)), mesh_axis_size=ndev)
        ks = jax.random.split(jax.random.fold_in(key, n * P), 5)
        fresh = jax.random.normal(ks[0], (n, P))
        sm = jax.random.bernoulli(ks[1], commit_frac, (n,))
        cm = jax.random.bernoulli(ks[2], commit_frac, (n,))
        # static bound on |C_t| for the indexed backend (the schedule knows
        # this in real runs; here the masks are concrete)
        k = max(1, int(np.sum(np.asarray(sm))), int(np.sum(np.asarray(cm))))
        ref_gbar = None
        for backend in backends:
            eng = DuDeEngine(spec=spec, n_workers=n, backend=backend,
                             index_width=k if backend == "indexed" else None,
                             mesh=mesh, axis_name="p" if mesh else None)
            # pre-populate buffers so the round moves real data
            state = eng.init()._replace(
                g_workers=jax.random.normal(ks[3], (n, P)),
                inflight=jax.random.normal(ks[4], (n, P)),
            )
            if mesh is not None:
                state = jax.device_put(state, eng.shardings())
            step = jax.jit(lambda s, f, a, b, e=eng: e.round(s, f, a, b))
            t = _time(lambda s, f, a, b: step(s, f, a, b)[1],
                      state, fresh, sm, cm)
            _, gbar = step(state, fresh, sm, cm)
            extra = {}
            if backend == "reference":
                ref_gbar = gbar
                extra["gbar_err_vs_reference"] = 0.0
            elif ref_gbar is not None:
                extra["gbar_err_vs_reference"] = float(
                    jnp.max(jnp.abs(gbar - ref_gbar)))
            # one full pass over the five streams (fresh + 2 slabs + gbar x2)
            full = (3 * n + 2) * P * F32
            traffic = {
                "reference": 9 * full,          # the unfused baseline itself
                "pallas": 2 * full,             # one read + one write each
                "indexed": 2 * (4 * k + 2) * P * F32,  # k-row gather/scatter
            }[backend]
            tag = "sharded" if sharded else "unsharded"
            rows.append({
                "name": f"engine/round/{backend}/n{n}_P{P}/{tag}",
                "backend": backend, "n": n, "P": spec.padded_size,
                "sharded": sharded, "devices": ndev,
                "us_per_call": 1e6 * t,
                "derived": 9 * full / traffic,
                "extra": extra,
            })
    return rows


def round_apply_sweep(backends=BACKENDS, opts=tuple(FLAT_OPTS),
                      point=(16, 1 << 14), commit_frac: float = 0.25,
                      sharded: bool = False) -> list[dict]:
    """Time the FUSED round+apply (flat-state training hot path) per
    backend x optimizer on identical inputs.

    The round streams the [n, P] slabs; the apply adds the [P] master
    params plus 0/1/2 slot slabs, all in one pass (one shard_map; the
    pallas backend folds the slot math into the kernel).  ``derived`` is
    the analytic traffic ratio of the UNFUSED baseline (round + separate
    optimizer apply re-reading g_bar/params/slots) over the fused pass.
    Correctness pulse: max |params| error vs. the reference backend.
    """
    mesh = None
    ndev = 1
    if sharded:
        ndev = jax.device_count()
        if ndev < 2:
            raise ValueError("sharded sweep needs >1 device")
        mesh = make_mesh((ndev,), ("p",))
    n, P = point
    rows = []
    key = jax.random.PRNGKey(7)
    spec = make_flat_spec(jnp.zeros((P,)), mesh_axis_size=ndev)
    ks = jax.random.split(key, 6)
    fresh = jax.random.normal(ks[0], (n, P))
    sm = jax.random.bernoulli(ks[1], commit_frac, (n,))
    cm = jax.random.bernoulli(ks[2], commit_frac, (n,))
    w0 = jax.random.normal(ks[5], (spec.padded_size,))
    # static active-set bound for the indexed backend, as in engine_sweep
    k = max(1, int(np.sum(np.asarray(sm))), int(np.sum(np.asarray(cm))))
    for opt_name in opts:
        fopt = FLAT_OPTS[opt_name]
        n_slots = len(jax.tree.leaves(fopt.init_slots(w0)))
        ref_w = None
        for backend in backends:
            eng = DuDeEngine(spec=spec, n_workers=n, backend=backend,
                             index_width=k if backend == "indexed" else None,
                             mesh=mesh, axis_name="p" if mesh else None)
            state = eng.init()._replace(
                g_workers=jax.random.normal(ks[3], (n, spec.padded_size)),
                inflight=jax.random.normal(ks[4], (n, spec.padded_size)),
            )
            w, ost = w0, fopt.init(w0)
            if mesh is not None:
                state = jax.device_put(state, eng.shardings())
                sh = flat_train_state_shardings(spec, mesh, ("p",), ost)
                w = jax.device_put(w, sh.params)
                ost = jax.device_put(ost, sh.opt)
            step = jax.jit(lambda s, f, a, b, w, o, e=eng, fo=fopt:
                           e.round_apply(s, f, a, b, w, o, fo))
            t = _time(lambda s, f, a, b, w, o: step(s, f, a, b, w, o)[2],
                      state, fresh, sm, cm, w, ost)
            _, _, w_new, _ = step(state, fresh, sm, cm, w, ost)
            extra = {}
            if backend == "reference":
                ref_w = w_new
                extra["w_err_vs_reference"] = 0.0
            elif ref_w is not None:
                extra["w_err_vs_reference"] = float(
                    jnp.max(jnp.abs(w_new - ref_w)))
            Pp = spec.padded_size
            # fused: one read + one write of every stream; unfused: the
            # ~9-pass round plus an apply re-reading g_bar/w/slots
            fused = 2 * ((3 * n + 2) * Pp + (1 + n_slots) * Pp) * F32
            unfused = (9 * (3 * n + 2) * Pp
                       + 2 * (2 + 2 * n_slots) * Pp) * F32
            tag = "sharded" if sharded else "unsharded"
            rows.append({
                "name": f"engine/round_apply/{backend}/{opt_name}/"
                        f"n{n}_P{Pp}/{tag}",
                "backend": backend, "optimizer": opt_name,
                "n": n, "P": Pp, "sharded": sharded, "devices": ndev,
                "us_per_call": 1e6 * t,
                "derived": unfused / fused,
                "extra": extra,
            })
    return rows


def session_dispatch_rows(algos=("dude", "fedbuff"), rounds: int = 30
                          ) -> list[dict]:
    """Time ``Trainer.step`` vs the raw prejitted flat step (same state,
    same batch): the session facade must be pure dispatch (ratio ~1)."""
    import jax.numpy as jnp  # noqa: F811 (explicit for the tiny config)
    from repro.api import Trainer, TrainerConfig
    from repro.models.config import ModelConfig

    cfg = ModelConfig(
        name="bench-lm", arch_type="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
        dtype=jnp.float32, remat=False, attn_chunk=16, n_workers=4,
    )
    n = cfg.n_workers
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (n, 2, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, 2, 32), 0, cfg.vocab_size),
    }
    sm = cm = jnp.ones(n, bool)
    rows = []
    for algo in algos:
        # facade path: the session object owns state + jit cache
        t = Trainer.create(TrainerConfig(arch=cfg, algo=algo, lr=0.01))
        t.step(batch, sm, cm)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(rounds):
            t.step(batch, sm, cm)
        jax.block_until_ready(t.state)
        facade = (time.perf_counter() - t0) / rounds

        # raw path: identical jitted step, state threaded by hand
        t2 = Trainer.create(TrainerConfig(arch=cfg, algo=algo, lr=0.01))
        raw = jax.jit(t2.step_fn, donate_argnums=(0,))
        state = t2.state
        state, _ = raw(state, batch, sm, cm)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(rounds):
            state, _ = raw(state, batch, sm, cm)
        jax.block_until_ready(state)
        rawt = (time.perf_counter() - t0) / rounds

        rows.append({
            "name": f"session/trainer_step_dispatch/{algo}",
            "algo": algo, "rounds": rounds,
            "us_per_call": 1e6 * facade,
            "derived": facade / rawt,      # facade overhead ratio (~1.0)
            "extra": {"raw_us_per_call": 1e6 * rawt},
        })
    return rows


def arrival_throughput_rows(points=((8, 1 << 14), (64, 1 << 16)),
                            loop_iters: int = 200) -> list[dict]:
    """Arrivals/sec of the async hot path vs the masked-step baseline.

    Per (n, P): the AsyncRunner's jitted arrival step (O(P): commit one
    worker's gradient + flat sgd apply) against a one-hot-masked
    ``round_apply`` (O(nP): the round-mode way to express one arrival,
    streaming every worker slab).  ``derived`` = runner arrivals/sec over
    masked arrivals/sec — the structural win of arrival granularity grows
    linearly in n.  Correctness pulse: with the arriving gradient latched
    in the inflight row, the one-hot round's g_bar equals commit's.
    Plus one end-to-end loop row: ``AsyncRunner.run`` arrivals/sec on a toy
    gradient, host event loop + DeviceQueue included.
    """
    from repro.core.algos import make_async_algo
    from repro.optim import FlatOptState
    from repro.runtime import FixedArrivals
    from repro.runtime.runner import AsyncRunner

    rows = []
    key = jax.random.PRNGKey(11)
    fopt = FLAT_OPTS["sgd"]
    for n, P in points:
        spec = make_flat_spec(jnp.zeros((P,)))
        eng = DuDeEngine(spec=spec, n_workers=n)
        algo = make_async_algo("dude", eng)
        ks = jax.random.split(jax.random.fold_in(key, n * P), 4)
        grad = jax.random.normal(ks[0], (spec.padded_size,))
        state = eng.init()._replace(
            g_workers=jax.random.normal(ks[1], (n, spec.padded_size)),
            inflight=jax.random.normal(ks[2], (n, spec.padded_size)))
        w0 = jax.random.normal(ks[3], (spec.padded_size,))
        ost = fopt.init(w0)
        worker = jnp.int32(1)

        @jax.jit
        def astep(srv, w, o, wk, g, algo=algo, fopt=fopt):
            srv, d = algo.arrival(srv, wk, g)
            t = o.step + 1
            w, sl = fopt.update(w, d, o.slots, t)
            return srv, w, FlatOptState(t, sl)

        t_arr = _time(lambda s, w, o, wk, g: astep(s, w, o, wk, g)[1],
                      state, w0, ost, worker, grad, reps=10)

        # masked-step baseline: same single arrival as a one-hot round
        onehot = jnp.zeros((n,), bool).at[1].set(True)
        fresh = jnp.broadcast_to(grad, (n, spec.padded_size))
        rstep = jax.jit(lambda s, f, a, b, w, o, e=eng, fo=fopt:
                        e.round_apply(s, f, a, b, w, o, fo))
        t_msk = _time(lambda s, f, a, b, w, o: rstep(s, f, a, b, w, o)[2],
                      state, fresh, onehot, onehot, w0, ost, reps=10)

        # correctness pulse: latch grad into the inflight row, then the
        # one-hot commit fold equals the per-arrival commit
        latched = state._replace(
            inflight=state.inflight.at[1].set(grad))
        _, g_commit = eng.commit(state, worker, grad)
        _, g_round = eng.round(latched, fresh, jnp.zeros((n,), bool), onehot)
        err = float(jnp.max(jnp.abs(g_commit - g_round)))
        rows.append({
            "name": f"runtime/arrival_throughput/commit_apply/n{n}_P{P}",
            "n": n, "P": spec.padded_size,
            "us_per_call": 1e6 * t_arr,
            "derived": t_msk / t_arr,   # runner-step speedup over masked
            "extra": {"arrivals_per_s": 1.0 / t_arr,
                      "masked_arrivals_per_s": 1.0 / t_msk,
                      "gbar_err_vs_round": err},
        })

    # end-to-end loop: host scheduling + DeviceQueue + grad included
    n, P0 = 8, 1 << 10
    tree = jnp.zeros((P0,))
    spec = make_flat_spec(tree)
    eng = DuDeEngine(spec=spec, n_workers=n)
    runner = AsyncRunner(eng, "dude", FLAT_OPTS["sgd"],
                         lambda p, b, k: (jnp.sum(p * b), p - b))
    st = runner.init_state(tree)
    sample = lambda i, rng: jnp.full((spec.padded_size,), float(i % 3))

    def loop_once():
        return runner.run(FixedArrivals(np.ones(n)), loop_iters, sample, st,
                          record_every=10 ** 9).state.params

    loop_once()  # compile/warm
    t0 = time.perf_counter()
    jax.block_until_ready(loop_once())
    t_loop = (time.perf_counter() - t0) / loop_iters
    rows.append({
        "name": f"runtime/arrival_throughput/runner_loop/n{n}_P{P0}",
        "n": n, "P": spec.padded_size,
        "us_per_call": 1e6 * t_loop,
        "derived": 1.0 / t_loop,        # arrivals/sec, loop included
        "extra": {"arrivals_per_s": 1.0 / t_loop, "iters": loop_iters},
    })

    # sparse-transport loop: the same end-to-end run over SparseRow commits.
    # The counters make the transport accountable: wire_bytes is what the
    # arrivals actually shipped, snap_encodes/snap_reuses expose the
    # delivery-side encode cache (the init zero-delta is encoded once and
    # shared by all n workers; every applying delivery re-encodes).
    eng_s = DuDeEngine(spec=spec, n_workers=n, commit_format="topk_ef",
                       sparse_meta=True)
    runner_s = AsyncRunner(eng_s, "dude", FLAT_OPTS["sgd"],
                           lambda p, b, k: (jnp.sum(p * b), p - b))
    st_s = runner_s.init_state(tree)

    def loop_sparse():
        return runner_s.run(FixedArrivals(np.ones(n)), loop_iters, sample,
                            st_s, record_every=10 ** 9)

    jax.block_until_ready(loop_sparse().state.params)  # compile/warm
    t0 = time.perf_counter()
    res = loop_sparse()
    jax.block_until_ready(res.state.params)
    t_sloop = (time.perf_counter() - t0) / loop_iters
    rows.append({
        "name": f"runtime/arrival_throughput/runner_loop_sparse/n{n}_P{P0}",
        "n": n, "P": spec.padded_size,
        "us_per_call": 1e6 * t_sloop,
        "derived": 1.0 / t_sloop,       # arrivals/sec, loop included
        "extra": {"arrivals_per_s": 1.0 / t_sloop, "iters": loop_iters,
                  "wire_rows": res.wire_rows, "wire_bytes": res.wire_bytes,
                  "wire_bytes_per_arrival":
                      res.wire_bytes / max(1, res.wire_rows),
                  "snap_encodes": res.snap_encodes,
                  "snap_reuses": res.snap_reuses},
    })
    return rows


def transport_sweep(n: int = 4, P0: int = 1 << 10,
                    total_iters: int = 40) -> list[dict]:
    """The framed multi-host hop: in-proc queues vs real loopback sockets.

    The same 2-link hosted run (``HostRunner.serve`` + two ``run_worker``
    client threads, topk_ef sparse snapshots, f32 commits) over
    ``InProcTransport.pair()`` and over connected ``socket.socketpair()``
    ends — arrivals/sec with the full protocol (handshake, snapshots,
    commits, heartbeats) and the framed byte totals each way.  The delta
    between the two rows is the OS socket cost; the in-proc row is the
    protocol-only ceiling.
    """
    import socket
    import threading

    from repro.runtime.hostloop import HostRunner, run_worker
    from repro.runtime.runner import AsyncRunner
    from repro.runtime.transport import InProcTransport, SocketTransport

    tree = jnp.zeros((P0,))
    spec = make_flat_spec(tree)
    grad_fn = lambda p, b, k: (jnp.sum(p * b), p - b)
    sample = lambda i, rng: jnp.full((spec.padded_size,), float(i % 3))
    groups = [tuple(range(n // 2)), tuple(range(n // 2, n))]

    def hosted_run(make_pair):
        eng = DuDeEngine(spec=spec, n_workers=n, commit_format="topk_ef",
                         sparse_meta=True)
        runner = AsyncRunner(eng, "dude", FLAT_OPTS["sgd"], grad_fn)
        pairs = [make_pair() for _ in range(2)]
        threads = [threading.Thread(
            target=lambda i=i: run_worker(lambda: pairs[i][1], groups[i],
                                          grad_fn, sample, spec,
                                          poll_s=0.02),
            daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        host = HostRunner(runner, heartbeat_s=2.0, dead_after_s=10.0,
                          poll_s=0.01)
        t0 = time.perf_counter()
        res = host.serve([p[0] for p in pairs], total_iters,
                         runner.init_state(tree), seed=0,
                         record_every=10 ** 9)
        dt = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=10)
        return res, dt

    def sock_pair():
        a, b = socket.socketpair()
        return (SocketTransport(a, timeout=10.0),
                SocketTransport(b, timeout=10.0))

    rows = []
    for label, make_pair in (("inproc", InProcTransport.pair),
                             ("socket", sock_pair)):
        res, dt = hosted_run(make_pair)
        per = dt / max(1, res.stats.iters)
        rows.append({
            "name": f"runtime/transport/{label}/n{n}_P{P0}",
            "n": n, "P": spec.padded_size,
            "us_per_call": 1e6 * per,
            "derived": 1.0 / per,       # arrivals/sec, wire included
            "extra": {"arrivals_per_s": 1.0 / per,
                      "iters": res.stats.iters,
                      "wire_sent": res.wire_sent,
                      "wire_recv": res.wire_recv,
                      "commit_bytes_per_arrival":
                          res.wire_recv / max(1, res.stats.iters)},
        })
    return rows


def sparse_transport_sweep(points=((8, 1 << 14), (64, 1 << 16)),
                           tiles_touched: int = 32) -> list[dict]:
    """SparseRow vs dense topk_ef commit transport on structurally sparse
    gradients (docs/engine.md "Sparse commit transport").

    Per (n, P), every worker's gradient touches the SAME ``tiles_touched``
    of the ``P/128`` tiles (a stable hot set — structured sparsity).  The
    shared set matters: the commit stream's error-feedback residual is one
    ``[P]`` vector, so each encode target touches the UNION of all
    previously committed tiles; a per-worker random set would grow that
    union past any fixed cap within a few commits.  The sparse engine's
    cap is ``2 * tiles_touched`` (headroom for the clear-set re-listing of
    previously touched tiles), which the shared hot set never overflows —
    keeping the pulse bitwise.

    * ``wire_bytes_sparse`` / ``wire_bytes_dense`` — actual bytes of one
      commit on the wire: ``sparse_wire_nbytes`` of the encoded row
      (``cap * (2k + 8) + 4``, O(k * tiles_touched)) vs the dense topk_ef
      row (``(2k + 4) * P/128``, O(P)); ``derived`` is the reduction;
    * ``fold_arrivals_per_s`` — the server-side hot path (``sparse_fold`` +
      flat sgd apply, touched tiles only) vs ``dense_arrivals_per_s``
      (dense ``commit`` + apply, streaming the whole row);
    * ``encode_us`` — the worker-side ``encode_sparse_commit`` cost;
    * ``gbar_err_vs_dense`` — max |g_bar| difference after one commit per
      worker, lockstep sparse vs dense.  MUST be exactly 0.0: the sparse
      fold runs the identical elementwise update on gathered lanes and
      scatter-sets the result, so it is bitwise equal to the dense commit.
    """
    from repro.core.algos import make_async_algo
    from repro.core.compression import sparse_wire_nbytes
    from repro.optim import FlatOptState

    rows = []
    key = jax.random.PRNGKey(31)
    fopt = FLAT_OPTS["sgd"]
    rng = np.random.default_rng(5)
    for n, P in points:
        spec = make_flat_spec(jnp.zeros((P,)))
        Pp = spec.padded_size
        dense = DuDeEngine(spec=spec, n_workers=n, commit_format="topk_ef")
        T = dense.codec.n_tiles(Pp)
        touch = min(tiles_touched, T)
        cap = min(2 * touch, T)
        sparse = DuDeEngine(spec=spec, n_workers=n, commit_format="topk_ef",
                            sparse_meta=True, sparse_cap=cap)
        # structurally sparse gradients: one shared hot-tile set (see above)
        k_commit = min(n, 8)
        ks = jax.random.split(jax.random.fold_in(key, n * P), 2)
        g_full = np.asarray(jax.random.normal(ks[0], (k_commit, Pp)))
        mask = np.zeros((T,), bool)
        mask[rng.choice(T, touch, replace=False)] = True
        gs = jnp.asarray(g_full * np.repeat(mask, dense.codec.tile))

        # dense hot path: commit + flat sgd apply (the runner's step)
        algo = make_async_algo("dude", dense)
        w0 = jax.random.normal(ks[1], (Pp,))
        ost = fopt.init(w0)

        @jax.jit
        def dstep(srv, w, o, wk, g, algo=algo, fopt=fopt):
            srv, d = algo.arrival(srv, wk, g)
            t = o.step + 1
            w, sl = fopt.update(w, d, o.slots, t)
            return srv, w, FlatOptState(t, sl)

        dst = dense.init()
        t_dense = _time(lambda s, w, o, wk, g: dstep(s, w, o, wk, g)[1],
                        dst, w0, ost, jnp.int32(1), gs[1 % k_commit],
                        reps=10)

        # sparse split: worker-side encode, server-side fold + apply
        enc = jax.jit(sparse.encode_sparse_commit)
        sst = sparse.init()
        t_enc = _time(lambda s, wk, g: enc(s, wk, g)[1].vals,
                      sst, jnp.int32(1), gs[1 % k_commit], reps=10)
        sst1, wire = enc(sst, jnp.int32(1), gs[1 % k_commit])

        @jax.jit
        def sstep(srv, w, o, wk, row, sparse=sparse, fopt=fopt):
            srv, d = sparse.sparse_fold(srv, wk, row)
            t = o.step + 1
            w, sl = fopt.update(w, d, o.slots, t)
            return srv, w, FlatOptState(t, sl)

        t_fold = _time(lambda s, w, o, wk, r: sstep(s, w, o, wk, r)[1],
                       sst1, w0, ost, jnp.int32(1), wire, reps=10)

        # bitwise pulse: one commit per worker, lockstep dense vs sparse
        dcommit = jax.jit(dense.commit)
        sfold = jax.jit(sparse.sparse_fold)
        d_st, s_st = dense.init(), sparse.init()
        err = 0.0
        for i in range(k_commit):
            d_st, g_d = dcommit(d_st, jnp.int32(i), gs[i])
            s_st, row = enc(s_st, jnp.int32(i), gs[i])
            s_st, g_s = sfold(s_st, jnp.int32(i), row)
            err = max(err, float(jnp.max(jnp.abs(g_d - g_s))))

        wire_sparse = sparse_wire_nbytes(row)
        wire_dense = dense.codec.commit_wire_bytes(Pp)
        rows.append({
            "name": f"compression/sparse_transport/n{n}_P{Pp}"
                    f"_touch{touch}_cap{cap}",
            "n": n, "P": Pp, "tiles": T,
            "tiles_touched": touch, "cap": cap,
            "us_per_call": 1e6 * t_fold,
            "derived": wire_dense / wire_sparse,   # wire-byte reduction
            "extra": {
                "wire_bytes_sparse": wire_sparse,
                "wire_bytes_dense": wire_dense,
                "wire_bytes_sparse_analytic":
                    sparse.codec.commit_wire_bytes(Pp, tiles_touched=cap),
                "fold_arrivals_per_s": 1.0 / t_fold,
                "dense_arrivals_per_s": 1.0 / t_dense,
                "fold_vs_dense": t_dense / t_fold,
                "encode_us": 1e6 * t_enc,
                "gbar_err_vs_dense": err,
            },
        })
    return rows


def commit_format_sweep(points=((8, 1 << 14), (64, 1 << 16))) -> list[dict]:
    """Compressed-slab commit formats vs f32 on the per-arrival hot path.

    Per (n, P) x ``commit_format`` (docs/engine.md "Compressed slabs"):

    * ``bytes_per_arrival`` — the analytic wire payload of ONE commit
      (``CommitCodec.commit_wire_bytes``): f32 moves ``4P``; int8_ef moves
      ``P + 4P/128`` (payload + per-tile scales, ~3.9x less); topk_ef moves
      ``(2k + 4) * P/128`` (k int8 values + k in-tile indices + scale per
      tile);
    * ``slab_bytes`` — resident bytes of one ``[n, P]`` worker slab plus its
      scale slab (``CommitCodec.slab_bytes``; the engine keeps two such
      slabs, stored + in-flight — same ratio);
    * ``arrivals_per_s`` — measured throughput of the jitted arrival step
      (``engine.commit`` + flat sgd apply, the AsyncRunner hot path), so the
      quantize/dequantize math is priced in, not assumed free;
    * ``gbar_err_vs_f32`` — max |g_bar| error against the f32 engine after
      one commit per worker on identical gradients, with the tile-wise
      quantization bound (``quant_bound``) it must respect for int8_ef
      (top-k drops lanes into EF, so its one-shot error is bounded by the
      dropped mass, not the quantization step).

    ``derived`` is the slab-residency reduction (f32 slab bytes / this
    format's).
    """
    from repro.core.algos import make_async_algo
    from repro.core.compression import COMMIT_FORMATS
    from repro.optim import FlatOptState

    rows = []
    key = jax.random.PRNGKey(23)
    fopt = FLAT_OPTS["sgd"]
    for n, P in points:
        spec = make_flat_spec(jnp.zeros((P,)))
        Pp = spec.padded_size
        ks = jax.random.split(jax.random.fold_in(key, n * P), 3)
        grad = jax.random.normal(ks[0], (Pp,))
        w0 = jax.random.normal(ks[1], (Pp,))
        # one distinct gradient per worker for the correctness pulse
        k_commit = min(n, 8)
        gs = jax.random.normal(ks[2], (k_commit, Pp))
        f32_t = None
        f32_gbar = None
        for fmt in COMMIT_FORMATS:
            eng = DuDeEngine(spec=spec, n_workers=n, commit_format=fmt)
            codec = eng.codec
            algo = make_async_algo("dude", eng)
            state = eng.init()
            ost = fopt.init(w0)

            @jax.jit
            def astep(srv, w, o, wk, g, algo=algo, fopt=fopt):
                srv, d = algo.arrival(srv, wk, g)
                t = o.step + 1
                w, sl = fopt.update(w, d, o.slots, t)
                return srv, w, FlatOptState(t, sl)

            t_arr = _time(lambda s, w, o, wk, g: astep(s, w, o, wk, g)[1],
                          state, w0, ost, jnp.int32(1), grad, reps=10)

            # correctness pulse: one commit per worker, vs the f32 engine
            st = state
            commit = jax.jit(eng.commit)
            for i in range(k_commit):
                st, gbar = commit(st, jnp.int32(i), gs[i])
            extra = {
                "arrivals_per_s": 1.0 / t_arr,
                "bytes_per_arrival": codec.commit_wire_bytes(Pp),
                "slab_bytes": codec.slab_bytes(n, Pp),
            }
            if fmt == "f32":
                f32_t, f32_gbar = t_arr, gbar
                extra["gbar_err_vs_f32"] = 0.0
            else:
                extra["gbar_err_vs_f32"] = float(
                    jnp.max(jnp.abs(gbar - f32_gbar)))
                # lane-wise bound: mean over committed rows of each row's
                # per-tile quantization bound (uncommitted rows are 0 = 0)
                bound = sum(np.repeat(np.asarray(codec.quant_bound(gs[i])),
                                      codec.tile) for i in range(k_commit)) / n
                extra["quant_bound_max"] = float(bound.max())
                extra["gbar_err_within_bound"] = (
                    fmt != "int8_ef"
                    or bool((np.abs(np.asarray(gbar - f32_gbar))
                             <= bound + 1e-7).all()))
                extra["bytes_reduction_vs_f32"] = (
                    4 * Pp / codec.commit_wire_bytes(Pp))
                extra["slab_reduction_vs_f32"] = (
                    4 * n * Pp / codec.slab_bytes(n, Pp))
                extra["arrivals_per_s_vs_f32"] = f32_t / t_arr
            rows.append({
                "name": f"compression/commit_format/{fmt}/n{n}_P{Pp}",
                "format": fmt, "n": n, "P": Pp,
                "us_per_call": 1e6 * t_arr,
                "derived": 4 * n * Pp / codec.slab_bytes(n, Pp),
                "extra": extra,
            })
    return rows


def unravel_sweep(arch: str = "qwen2_0_5b", shape=(2, 4),
                  n_workers: int | None = None) -> list[dict]:
    """Replicated vs TP-native param exchange on a (data, model) host mesh.

    Both directions are swept — ``unravel`` ([P] shards -> TP-layout leaves,
    the forward feed) and ``ravel_stacked`` (TP-layout grad leaves ->
    [n, P] slab shards, the reverse path) — on the real ``param_shardings``
    of ``arch``'s smoke config, so the per-leaf exchange plan exercises
    genuine Megatron-TP layouts (embedding, fused-QKV-like kernels, norms).
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_config
    from repro.models import lm_init
    from repro.sharding import (
        flat_slab_shardings, flat_vec_sharding, param_shardings,
    )

    d, m = shape
    if jax.device_count() < d * m:
        print(f"# unravel sweep skipped: needs {d * m} devices")
        return []
    mesh = make_mesh((d, m), ("data", "model"), devices=jax.devices()[: d * m])
    axes = ("data", "model")
    cfg = get_config(arch).smoke()
    n = n_workers or cfg.n_workers
    params = lm_init(jax.random.PRNGKey(0), cfg)
    spec = make_flat_spec(params, mesh_axis_size=d * m)
    p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
    plan = spec.tp_plan(mesh, p_sh, axes=axes)

    flat = jax.device_put(spec.ravel(params),
                          flat_vec_sharding(spec, mesh, axes))
    repl_sh = NamedSharding(mesh, PartitionSpec())
    unravel_repl = jax.jit(lambda f: spec.unravel(
        jax.lax.with_sharding_constraint(f, repl_sh)))
    unravel_tp = jax.jit(lambda f: spec.unravel_sharded(f, mesh, plan=plan))

    oracle = jax.tree.leaves(unravel_repl(flat))
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(unravel_tp(flat)), oracle))

    k = plan.k
    repl_gather = plan.full_vector_bytes * (k - 1) // k  # all-gather payload
    footprint = {  # per-device peak live bytes for the params feed
        "replicated": plan.full_vector_bytes,
        "tp": plan.peak_bytes,
    }
    moved = {"replicated": repl_gather, "tp": plan.ring_bytes}
    rows = []
    for layout, fn in (("replicated", unravel_repl), ("tp", unravel_tp)):
        t = _time(lambda f: jax.tree.leaves(fn(f))[0], flat)
        rows.append({
            "name": f"exchange/unravel/{layout}/{arch}_{d}x{m}",
            "layout": layout, "P": spec.padded_size, "devices": d * m,
            "us_per_call": 1e6 * t,
            "derived": plan.full_vector_bytes / footprint[layout],
            "extra": {
                "peak_live_bytes_per_device": footprint[layout],
                "exchange_bytes_per_device": moved[layout],
                "max_leaf_gather_bytes": plan.max_leaf_segment_bytes(),
                "err_vs_replicated": 0.0 if layout == "replicated" else err,
            },
        })

    # reverse path: TP-layout stacked grads -> [n, P] slab shards.  Each
    # layout is fed ITS OWN natural input placement (the replicated path's
    # grads come out of a replicated-params forward; the tp path's out of a
    # TP forward), and both are checked against the placement-free eager
    # oracle — letting GSPMD auto-partition the ravel from TP-placed leaves
    # is not only O(nP) per device, it MISCOMPILES on this jax version
    # (reshape+concat over mixed 2-D-sharded operands returns permuted
    # rows; the explicit shard_map ring sidesteps the partitioner).
    stree = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(n)]), params)
    want = spec.ravel_stacked(stree)  # eager oracle, placement-free
    g_sh = spec.treedef.unflatten(
        [NamedSharding(mesh, PartitionSpec(None, *lf.entries))
         for lf in plan.leaves])
    stree_tp = jax.device_put(stree, g_sh)
    stree_repl = jax.device_put(stree, NamedSharding(mesh, PartitionSpec()))
    slab_sh = flat_slab_shardings(
        jax.ShapeDtypeStruct((n, spec.padded_size), jnp.float32),
        spec, mesh, axes)
    ravel_repl = jax.jit(lambda t: jax.lax.with_sharding_constraint(
        spec.ravel_stacked(t), slab_sh))
    ravel_tp = jax.jit(lambda t: spec.ravel_stacked_sharded(
        t, mesh, plan=plan))
    for layout, fn, inp in (("replicated", ravel_repl, stree_repl),
                            ("tp", ravel_tp, stree_tp)):
        rerr = float(jnp.max(jnp.abs(fn(inp) - want)))
        t = _time(fn, inp)
        full = n * plan.full_vector_bytes
        peak = full if layout == "replicated" else n * plan.peak_bytes
        rows.append({
            "name": f"exchange/ravel_stacked/{layout}/{arch}_{d}x{m}",
            "layout": layout, "P": spec.padded_size, "n": n,
            "devices": d * m, "us_per_call": 1e6 * t,
            "derived": full / peak,
            "extra": {"err_vs_oracle": rerr},
        })
    return rows


def scenario_grid_rows(iters: int = 150,
                       dropout_rates=(0.0, 0.2),
                       hets=(1.0, 5.0),
                       algos=("dude", "dude_hinge", "dude_poly",
                              "vanilla_asgd")) -> list[dict]:
    """BENCH_10 scenario grid: dropout-rate x heterogeneity x staleness
    rule, end-to-end through ``AsyncRunner`` under a ``ClientStateProcess``
    (mid-round dropout + reconnect-from-stale-snapshot).  Each cell runs the
    N-worker closed-form quadratic so ``derived`` is the exact
    ||grad F||^2 at the final iterate — a convergence-quality number, not a
    timing — while ``us_per_call`` keeps the loop's arrival latency and
    ``extra`` records tau_max plus the trace's dropout telemetry."""
    from repro.optim import flat_sgd
    from repro.runtime import ClientStateProcess, FixedArrivals
    from repro.runtime.runner import AsyncRunner

    n, P = 8, 64
    rows = []
    for het in hets:
        rng = np.random.default_rng(17)
        A = np.stack([np.diag(rng.uniform(0.5, 2.0, P)) for _ in range(n)])
        b = np.stack([rng.normal(size=P) * het for _ in range(n)])
        Abar, bbar = A.mean(axis=0), b.mean(axis=0)
        Aj = jnp.asarray(A, jnp.float32)
        bj = jnp.asarray(b, jnp.float32)

        def grad_fn(params, batch, key, Aj=Aj, bj=bj):
            Ai, bi = Aj[batch], bj[batch]
            g = Ai @ params - bi + 0.05 * jax.random.normal(key, (P,))
            return 0.5 * params @ Ai @ params - bi @ params, g

        sample_fn = (lambda i, rng_: jnp.int32(i))

        for drop in dropout_rates:
            for name in algos:
                eng = DuDeEngine(spec=make_flat_spec(jnp.zeros(P)),
                                 n_workers=n)
                runner = AsyncRunner(eng, name, flat_sgd(0.03), grad_fn)
                st = runner.init_state(jnp.zeros(P))
                proc = ClientStateProcess(
                    FixedArrivals(np.linspace(0.6, 2.0, n)),
                    seed=23, dropout_rate=drop,
                    reconnect_mean=1.0 if drop else None)
                t0 = time.perf_counter()
                res = runner.run(proc, iters, sample_fn, st, seed=0,
                                 record_every=10 ** 9)
                jax.block_until_ready(res.state.params)
                t_loop = (time.perf_counter() - t0) / iters
                w = np.asarray(eng.spec.unravel(res.state.params))
                stats = res.stats.trace.event_stats()
                rows.append({
                    "name": f"scenario_grid/het{het}/drop{drop}/{name}",
                    "n": n, "P": eng.spec.padded_size,
                    "us_per_call": 1e6 * t_loop,
                    "derived": float(np.sum((Abar @ w - bbar) ** 2)),
                    "extra": {"tau_max": int(res.tau_max),
                              "arrivals_per_s": 1.0 / t_loop,
                              "dropouts": stats.get("dropouts", 0),
                              "outage_time": stats.get("outage_time", 0.0)},
                })
    return rows


def run(backend: str = "all") -> list[dict]:
    backends = BACKENDS if backend == "all" else (backend,)
    rows = engine_sweep(backends)
    rows += round_apply_sweep(backends)
    rows += session_dispatch_rows()
    rows += arrival_throughput_rows()
    rows += scenario_grid_rows()
    rows += commit_format_sweep()
    rows += sparse_transport_sweep()
    rows += transport_sweep()
    if jax.device_count() > 1:
        rows += engine_sweep(backends, sharded=True)
        rows += round_apply_sweep(backends, sharded=True)
        rows += unravel_sweep()
    else:
        print("# sharded engine + unravel sweeps skipped: 1 device "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    key = jax.random.PRNGKey(0)

    # --- dude_update: fused streaming op ---------------------------------
    n, P = 8, 1 << 14
    ks = jax.random.split(key, 8)
    fresh = jax.random.normal(ks[0], (n, P))
    gw = jax.random.normal(ks[1], (n, P)).astype(jnp.bfloat16)
    infl = jax.random.normal(ks[2], (n, P)).astype(jnp.bfloat16)
    gbar = jax.random.normal(ks[3], (P,))
    w = jax.random.normal(ks[4], (P,))
    cm = jax.random.bernoulli(ks[5], 0.5, (n,))
    sm = jax.random.bernoulli(ks[6], 0.5, (n,))
    t = _time(lambda *a: dude_update(*a, eta=0.1, interpret=True),
              cm, sm, fresh, gw, infl, gbar, w)
    out = dude_update(cm, sm, fresh, gw, infl, gbar, w, eta=0.1, interpret=True)
    rb, *_ = ref.dude_update_ref(gbar, gw, infl, fresh, sm, cm, n)
    err = float(jnp.max(jnp.abs(out[2] - rb)))
    # XLA unfused: ~9 passes over the streams; kernel: 1 read + 1 write each
    xla_bytes = 9 * (2 * n * P * 2 + 2 * P * F32)
    kern_bytes = 2 * (2 * n * P * 2 + n * P * F32 + 2 * P * F32)
    rows.append({
        "name": "kernels/dude_update/fusion_ratio",
        "us_per_call": 1e6 * t,
        "derived": xla_bytes / kern_bytes,
        "extra": {"allclose_err": err},
    })

    # --- flash attention: S^2 HBM traffic removal ------------------------
    B, S, H, K, hd = 1, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    kk = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    t = _time(lambda *a: flash_attention(*a, blk_q=64, blk_k=64,
                                         interpret=True), q, kk, v)
    o = flash_attention(q, kk, v, blk_q=64, blk_k=64, interpret=True)
    err = float(jnp.max(jnp.abs(o - ref.flash_attention_ref(q, kk, v))))
    io_bytes = (2 * B * S * H * hd + 2 * B * S * K * hd) * F32
    xla_bytes = io_bytes + 2 * B * H * S * S * F32  # materialized scores r+w
    rows.append({
        "name": "kernels/flash_attention/hbm_ratio",
        "us_per_call": 1e6 * t,
        "derived": xla_bytes / io_bytes,
        "extra": {"allclose_err": err},
    })

    # --- flash decode: window skip ----------------------------------------
    Sc, W = 2048, 256
    kc = jax.random.normal(ks[1], (B, Sc, K, hd))
    vc = jax.random.normal(ks[2], (B, Sc, K, hd))
    qd = jax.random.normal(ks[0], (B, 1, H, hd))
    t = _time(lambda *a: flash_decode(*a, window=W, blk_s=256, interpret=True),
              qd, kc, vc, jnp.int32(Sc))
    o = flash_decode(qd, kc, vc, Sc, window=W, blk_s=256, interpret=True)
    # full-cache read vs window-only blocks
    rows.append({
        "name": "kernels/flash_decode/window_skip_ratio",
        "us_per_call": 1e6 * t,
        "derived": Sc / W,
        "extra": {},
    })
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="all",
                    choices=list(BACKENDS) + ["all"],
                    help="ServerEngine backend(s) to sweep")
    ap.add_argument("--json-out", default="benchmarks/BENCH_10.json",
                    help="write rows as machine-readable JSON here "
                         "('' disables)")
    args = ap.parse_args()
    rows = run(backend=args.backend)
    for r in rows:
        extra = r.get("extra") or {}
        tail = "".join(f",{k}={v:.3g}" for k, v in extra.items())
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.3f}{tail}")
    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump({
                "pr": 10,
                "device_count": jax.device_count(),
                "platform": jax.default_backend(),
                "rows": rows,
            }, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json_out}")
