"""Production training driver (DESIGN.md mode B): semi-async ROUND training
or event-driven PER-ARRIVAL training (``--async``) on whatever mesh is
available, through the one ``api.Trainer`` session — every server algorithm
in the ``core/algos.py`` registries runs the same mesh-native flat engine
state.

On the real cluster this runs under the 16x16 / 2x16x16 production meshes
(see dryrun.py for the lowering proof); on this CPU container it runs the
same code path on a 1-device mesh at reduced scale (or a host-platform
multi-device mesh via --mesh and XLA_FLAGS=--xla_force_host_platform_device_count=N).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --rounds 50 --seq-len 64 --per-worker-batch 2 --algo dude
  # a Table-1 baseline through the same engine path:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --rounds 50 --algo fedbuff
  # event-driven per-arrival training (docs/async.md): exponential
  # stragglers, one engine.commit + optimizer apply per gradient arrival
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --async --arrival exp --rounds 50 --algo dude --trace-out trace.json
  # bit-exact replay of that run's arrival schedule:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --async --arrival trace --trace-in trace.json --rounds 50 --algo dude
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (CheckpointPolicy, ConfigError, Trainer,
                       TrainerConfig, TransportPolicy)
from repro.api.config import OPTIMIZERS
from repro.core import (
    ASYNC_ALGOS, BACKENDS, COMMIT_FORMATS, ROUND_ALGOS, delay_stats,
    make_round_schedule,
    truncated_normal_speeds,
)
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.sampling import make_worker_sample_fn
from repro.runtime import (
    ARRIVAL_KINDS, SCENARIO_KINDS, ExponentialArrivals, FixedArrivals,
    make_arrivals,
)


def parse_mesh(spec: str):
    """``--mesh`` spec -> Mesh: "none" (default), or "DxM" for a
    (data, model) host mesh, e.g. "2x4" under an 8-device host platform."""
    if spec in ("none", ""):
        return None
    d, m = (int(x) for x in spec.split("x"))
    return make_mesh((d, m), ("data", "model"))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config variant (CPU-scale)")
    ap.add_argument("--rounds", type=int, default=100,
                    help="server iterations (rounds, or applied arrivals "
                         "under --async)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--opt", default="sgd", choices=sorted(OPTIMIZERS))
    ap.add_argument("--algo", default="dude",
                    choices=sorted(set(ROUND_ALGOS) | set(ASYNC_ALGOS)),
                    help="server update rule (core/algos registries): round "
                         "rules drive the masked round step, arrival rules "
                         "need --async; 'dude' runs either way")
    ap.add_argument("--server-backend", default="reference",
                    choices=list(BACKENDS),
                    help="ServerEngine update path for the DuDe round "
                         "(pallas = fused kernel; interpret mode on CPU)")
    ap.add_argument("--commit-format", default="f32",
                    choices=list(COMMIT_FORMATS),
                    help="engine slab storage / commit wire format: f32, "
                         "int8_ef (tiled int8 + error feedback) or topk_ef "
                         "(per-tile magnitude top-k before int8) — "
                         "docs/engine.md 'Compressed slabs'")
    ap.add_argument("--sparse-transport", action="store_true",
                    help="topk_ef only: ship commits as index-carrying "
                         "SparseRows and fold only touched tiles — "
                         "O(k * tiles_touched) ingress instead of O(P) "
                         "(docs/engine.md 'Sparse commit transport')")
    ap.add_argument("--sparse-cap", type=int, default=None,
                    help="static touched-tile slots per SparseRow commit "
                         "(default: all tiles; smaller caps bound wire "
                         "bytes, overflow re-enters via error feedback)")
    ap.add_argument("--mesh", default="none",
                    help='"DxM" (data x model) host mesh, or "none"')
    ap.add_argument("--params-layout", default="replicated",
                    choices=["replicated", "tp"],
                    help="forward param feed: 'replicated' = one [P] "
                         "all-gather per step; 'tp' = TP-native exchange "
                         "from the P-shards (no full [P] on any device; "
                         "needs --mesh)")
    ap.add_argument("--fedbuff-buffer-size", type=int, default=4)
    # ------------------------------------------------- async runtime flags
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="event-driven per-arrival training (AsyncRunner): "
                         "one engine.commit + flat optimizer apply per "
                         "gradient arrival (docs/async.md)")
    ap.add_argument("--arrival", default="fixed", choices=list(ARRIVAL_KINDS),
                    help="arrival process: 'fixed' = the paper's fixed-"
                         "speed model (from --speed-std), 'exp' = "
                         "exponential durations (stragglers in the tail), "
                         "'trace' = replay --trace-in")
    ap.add_argument("--arrival-mean", type=float, default=1.0,
                    help="exp arrivals: scale on the per-worker mean "
                         "durations (drawn from the speed model)")
    ap.add_argument("--trace-in", default=None,
                    help="ArrivalTrace JSON to replay (--arrival trace)")
    ap.add_argument("--trace-out", default=None,
                    help="record this run's ArrivalTrace JSON here")
    ap.add_argument("--scenario", default="none",
                    choices=list(SCENARIO_KINDS),
                    help="client-state scenario wrapped around the arrival "
                         "process (--async only): dropout = mid-round "
                         "disconnect + reconnect-from-stale-snapshot, "
                         "partial = partial-gradient completeness, "
                         "sin/lognormal/skew = availability cycles, chaos = "
                         "all of it (docs/async.md 'Client-state "
                         "scenarios'); trace replays carry their own "
                         "recorded client state")
    ap.add_argument("--max-in-flight", type=int, default=None,
                    help="bound on concurrent dispatched-but-unarrived "
                         "gradient jobs (back-pressure on simultaneously "
                         "stale work; default: all workers)")
    # ---------------------------------------------- multi-host server flags
    ap.add_argument("--serve", default=None, metavar="HOST:PORT",
                    help="multi-host server mode (needs --async): listen "
                         "here, accept --expect-links worker processes "
                         "(launch/worker.py), and drive the server "
                         "iteration from their commit frames "
                         "(docs/async.md 'Multi-host transport')")
    ap.add_argument("--expect-links", type=int, default=1,
                    help="worker PROCESSES to wait for before serving "
                         "(each may carry several logical workers)")
    ap.add_argument("--link-timeout", type=float, default=120.0,
                    help="seconds to wait for the initial links")
    ap.add_argument("--heartbeat-s", type=float, default=5.0,
                    help="PING a link silent this long")
    ap.add_argument("--dead-after-s", type=float, default=20.0,
                    help="declare a link dead after this much silence")
    ap.add_argument("--max-wall-s", type=float, default=None,
                    help="hard wall-clock bound on the serving loop")
    ap.add_argument("--replay-check", action="store_true",
                    help="after serving, replay the recorded trace through "
                         "the single-process AsyncRunner and assert the "
                         "final [P] params and per-arrival digests match "
                         "bit-for-bit")
    ap.add_argument("--speed-std", type=float, default=1.0,
                    help="worker speed heterogeneity (paper std)")
    ap.add_argument("--heterogeneity", type=float, default=1.0,
                    help="data distribution skew across workers")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    try:
        config = TrainerConfig(
            arch=args.arch, smoke=args.smoke, algo=args.algo,
            optimizer=args.opt, lr=args.lr,
            server_backend=args.server_backend,
            commit_format=args.commit_format,
            sparse_transport=args.sparse_transport,
            sparse_cap=args.sparse_cap,
            mesh=parse_mesh(args.mesh),
            params_layout=args.params_layout,
            fedbuff_buffer_size=args.fedbuff_buffer_size,
            max_in_flight=args.max_in_flight,
            scenario=args.scenario,
            seed=args.seed,
            checkpoint=CheckpointPolicy(directory=args.ckpt_dir,
                                        every=args.ckpt_every),
            transport=TransportPolicy(heartbeat_s=args.heartbeat_s,
                                      dead_after_s=args.dead_after_s),
        )
    except ConfigError as e:
        ap.error(str(e))
    if args.serve and not args.async_mode:
        ap.error("--serve needs --async (the multi-host loop is arrival-"
                 "granularity)")
    if args.scenario != "none" and not args.async_mode:
        ap.error("--scenario needs --async (client state is per-arrival)")

    if args.resume and args.ckpt_dir:
        trainer = Trainer.restore(args.ckpt_dir, config)
        print("[train] resumed (auto-format restore)")
    else:
        trainer = Trainer.create(config)
    cfg = trainer.cfg
    n = cfg.n_workers
    mode = "async" if args.async_mode else "rounds"
    print(f"[train] arch={cfg.name} algo={args.algo} mode={mode} workers={n} "
          f"devices={jax.device_count()} mesh={args.mesh} "
          f"server-backend={args.server_backend}")
    print(f"[train] params={trainer.param_count():,}")

    speeds = truncated_normal_speeds(n, std=args.speed_std, seed=args.seed + 1)
    # the one batch pipeline every mode shares — identical bytes for a given
    # (worker, rng) in the server, a remote worker process, and a replay
    sample_fn = make_worker_sample_fn(
        cfg, seq_len=args.seq_len, per_worker_batch=args.per_worker_batch,
        heterogeneity=args.heterogeneity, seed=args.seed)

    t0 = time.time()

    if args.serve:
        # ----------------------- multi-host serving (real worker links) ----
        from repro.runtime.hostloop import accept_links, poll_accept_fn
        from repro.runtime.transport import serve_listener
        host, port = args.serve.rsplit(":", 1)
        listener = serve_listener(host, int(port))
        print(f"[serve] listening on {args.serve}, waiting for "
              f"{args.expect_links} link(s)")
        links = accept_links(listener, args.expect_links,
                             timeout=args.link_timeout)
        res = trainer.serve_async(links, args.rounds,
                                  record_every=args.log_every,
                                  seed=args.seed,
                                  accept_fn=poll_accept_fn(listener),
                                  max_wall_s=args.max_wall_s)
        listener.close()
        for t, it, loss in zip(res.times, res.iters, res.losses):
            print(f"[arrival it={it:5d}] loss={loss:.4f}")
        if args.trace_out:
            res.trace.save(args.trace_out)
            print(f"[serve] wrote arrival trace -> {args.trace_out}")
        if args.ckpt_dir:
            print(f"[serve] checkpoint -> {trainer.save()}")
        replay_ok = None
        if args.replay_check:
            from repro.runtime import TraceArrivals
            fresh = Trainer.create(config)
            rep = fresh.run_async(
                TraceArrivals(res.trace), args.rounds, sample_fn,
                record_every=args.log_every, seed=args.seed,
                key_mode="worker", record_digests=True)
            params_ok = bool(np.array_equal(
                np.asarray(rep.state.params), np.asarray(res.state.params)))
            digest_ok = rep.digests == res.trace.digest
            replay_ok = params_ok and digest_ok
            print(f"[serve] replay-check: params_bitwise={params_ok} "
                  f"digests={digest_ok}")
        print(json.dumps({
            "arch": cfg.name, "algo": args.algo, "mode": "serve",
            "iters": int(res.stats.iters),
            "arrivals": int(res.stats.arrivals),
            "tau_max": int(res.tau_max),
            "dropouts": int(res.dropouts),
            "reconnects": int(res.reconnects),
            "dropped_workers": list(res.dropped_workers),
            "wire_sent": int(res.wire_sent), "wire_recv": int(res.wire_recv),
            "last_loss": float(res.losses[-1]) if len(res.losses) else None,
            "replay_ok": replay_ok,
            "wall_s": round(time.time() - t0, 1),
        }))
        if args.replay_check and not replay_ok:
            raise SystemExit("[serve] replay-check FAILED")
        return

    if args.async_mode:
        # --------------------------- event-driven per-arrival training ----
        if args.arrival == "fixed":
            process = FixedArrivals.from_speeds(speeds)
        elif args.arrival == "exp":
            process = ExponentialArrivals(
                n, mean=np.asarray(speeds.times) * args.arrival_mean,
                seed=args.seed + 2)
        else:
            if args.trace_in is None:
                ap.error("--arrival trace needs --trace-in")
            process = make_arrivals("trace", n, trace=args.trace_in)

        res = trainer.run_async(process, args.rounds, sample_fn,
                                record_every=args.log_every)
        for t, it, loss in zip(res.times, res.iters, res.losses):
            print(f"[arrival it={it:5d}] loss={loss:.4f} t_sim={t:.2f}")
        if args.trace_out:
            res.trace.save(args.trace_out)
            print(f"[train] wrote arrival trace -> {args.trace_out}")
        if args.ckpt_dir:
            # the runner owns the arrival loop, so the round-cadence
            # maybe_save() never fires mid-run; always persist the final
            # state when a checkpoint directory is configured
            print(f"[train] checkpoint -> {trainer.save()}")
        print(json.dumps({
            "arch": cfg.name, "algo": args.algo, "mode": "async",
            "arrival": args.arrival, "scenario": args.scenario,
            "iters": int(res.stats.iters),
            "arrivals": int(res.stats.arrivals),
            "tau_max": int(res.tau_max),
            "max_in_flight": int(res.stats.max_in_flight),
            "first_loss": float(res.losses[0]) if len(res.losses) else None,
            "last_loss": float(res.losses[-1]) if len(res.losses) else None,
            "wall_s": round(time.time() - t0, 1),
            **({"scenario_stats": res.trace.event_stats()}
               if res.trace is not None and res.trace.events else {}),
        }))
        return

    # ------------------------------------------- masked round training ----
    sch = make_round_schedule(speeds, args.rounds)
    print(f"[train] schedule: {delay_stats(sch)}")
    rng = np.random.default_rng(args.seed)

    def round_batch():
        per = [sample_fn(i, rng) for i in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    history = []
    for r in range(sch.rounds):
        metrics = trainer.step(round_batch(),
                               sch.start[r], sch.commit[r])
        loss = float(metrics["loss"])
        history.append(loss)
        if r % args.log_every == 0:
            print(f"[round {r:4d}] loss={loss:.4f} "
                  f"({(time.time() - t0) / (r + 1):.2f}s/round)")
        trainer.maybe_save()

    print(json.dumps({
        "arch": cfg.name, "algo": args.algo, "mode": "rounds",
        "rounds": sch.rounds,
        "first_loss": history[0], "last_loss": history[-1],
        "wall_s": round(time.time() - t0, 1),
    }))


if __name__ == "__main__":
    main()
