"""Multi-host worker entrypoint: compute gradients for a remote server.

The client half of the multi-host runtime (docs/async.md "Multi-host
transport"): dials a ``launch/train.py --serve`` server, claims a range of
logical workers, and loops — decode the model snapshot the server ships,
draw the worker's local batch, compute one stochastic gradient, frame it
back as a commit.  No engine state lives here: the worker needs only the
model config (to build the same ``FlatSpec`` and loss), so a worker
process is cheap enough to run many logical workers.

Determinism: the batch and PRNG key of worker ``w``'s job ``j`` depend
only on ``(seed, w, j)`` (``runtime.runner.worker_key`` /
``worker_rng``), and the snapshot decode / gradient / ravel jits are the
same expressions the server's replay runs — so the single-process
``AsyncRunner`` replaying the recorded trace reproduces this process's
commits bit-for-bit.

Example (against the smoke server in the CI multi-host job)::

  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --async --serve 127.0.0.1:7781 --expect-links 2 \
      --commit-format topk_ef --sparse-transport --rounds 40 \
      --trace-out trace.json --replay-check &
  PYTHONPATH=src python -m repro.launch.worker --arch qwen2_0_5b --smoke \
      --connect 127.0.0.1:7781 --workers 0-1 &
  PYTHONPATH=src python -m repro.launch.worker --arch qwen2_0_5b --smoke \
      --connect 127.0.0.1:7781 --workers 2-3
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_config
from repro.core.flatten import make_flat_spec
from repro.launch.cache import use_compile_cache
from repro.launch.sampling import make_worker_sample_fn
from repro.launch.steps import abstract_params
from repro.models import loss_fn
from repro.runtime.hostloop import run_worker
from repro.runtime.transport import connect
from repro.sharding import make_shard_hook


def parse_workers(spec: str) -> tuple:
    """``"0-3"`` (inclusive) or ``"0,2,5"`` -> logical worker ids."""
    if "-" in spec:
        lo, hi = (int(x) for x in spec.split("-"))
        return tuple(range(lo, hi + 1))
    return tuple(int(x) for x in spec.split(","))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="the --serve address of the server process")
    ap.add_argument("--workers", required=True,
                    help='logical worker ids this process serves: "0-3" '
                         '(inclusive range) or "0,2,5"')
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--heterogeneity", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="must match the server's --seed (fixes the "
                         "per-worker data distributions; gradient keys "
                         "come from the server's WELCOME seed)")
    ap.add_argument("--axis-size", type=int, default=1,
                    help="the server engine's P-axis mesh size (pads the "
                         "local FlatSpec identically; 1 for a meshless "
                         "server)")
    ap.add_argument("--timeout", type=float, default=30.0,
                    help="per send/recv socket timeout")
    ap.add_argument("--max-reconnects", type=int, default=3,
                    help="re-dial attempts after a dropped connection "
                         "(0 = die with the first drop)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    workers = parse_workers(args.workers)
    for w in workers:
        if not 0 <= w < cfg.n_workers:
            ap.error(f"worker {w} outside [0, {cfg.n_workers})")

    spec = make_flat_spec(abstract_params(cfg),
                          mesh_axis_size=args.axis_size)
    sample_fn = make_worker_sample_fn(
        cfg, seq_len=args.seq_len, per_worker_batch=args.per_worker_batch,
        heterogeneity=args.heterogeneity, seed=args.seed)
    # the same gradient the server's Trainer computes (meshless hook) — the
    # replay oracle depends on this being the identical jitted expression
    shard = make_shard_hook(None)

    def grad_fn(params, batch, key):
        (_, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, shard=shard), has_aux=True
        )(params)
        return metrics["loss"], grads

    host, port = args.connect.rsplit(":", 1)
    print(f"[worker] {args.arch} workers={list(workers)} -> {args.connect}")
    t0 = time.time()
    stats = run_worker(
        lambda: connect(host, int(port), timeout=args.timeout),
        workers, grad_fn, sample_fn, spec,
        max_reconnects=args.max_reconnects)
    stats["workers"] = list(workers)
    stats["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(stats))


if __name__ == "__main__":
    main()
