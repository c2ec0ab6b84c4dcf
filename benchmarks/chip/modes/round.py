"""Round mode: ``Trainer.step``, one semi-asynchronous DuDe round per
call (n per-worker backward passes, the ravel into the ``[n, P]`` slab, the
fused round kernel and the SGD apply), in a closed loop.  A mix with a
``mesh`` runs the program's P-sharded engine on that mesh of chips.

Set-up drives the first ``checked_steps`` rounds through ``Trainer.step``
on the first batches of the pool, and reads the step losses, the per-leaf
norms of ``g_bar`` after the first round that commits (the direction the
optimizer gets), and of the parameters' change after the last checked
step.  The window goes on from there with the same object.  The loop keeps
the mix's ``ahead`` rounds (seconds of work) queued behind the executing
one: after dispatching round k it reads round k-ahead's loss, so a stall
of the host does not leave the chip without work.  It sends no more once
the rounds it has sent would run past the window's length, waits for all
of them, and counts all of them over all of that time.
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np

import harness
import traffic as gen

# rounds of schedule made per second of window: more than the chip can run
ROUNDS_PER_S = 200


class Mode:
    kind = "round"

    def __init__(self, run: harness.Run):
        self.run = run
        mix = run.mix
        self.n = mix["n_workers"]
        self.ahead = int(mix.get("ahead", 1))
        self.checked = mix["checked_steps"]
        rounds = self.checked + int(ROUNDS_PER_S * run.seconds) + 1
        self.start, self.commit = gen.make_round_schedule(
            run.traffic.times, rounds)
        self.first_commit = int(np.argmax(self.commit.any(axis=1)))
        self.feed = None        # host batch -> device batch (set-up)
        if not self.first_commit < self.checked:
            raise ValueError("no round commits within the checked steps")

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        import jax
        from repro.api import Trainer
        run = self.run
        mesh = harness.make_mesh(run)
        self.trainer = Trainer.create(
            harness.trainer_config(run, self.n, mesh),
            params=harness.program_weights(run))
        self.norms = harness.LeafNorms(run,
                                       int(self.trainer.state.params.shape[0]))
        feed = self.feed or jax.device_put
        if mesh is not None and self.feed is None:
            # every device of the mesh gets the whole round's batch
            from jax.sharding import NamedSharding, PartitionSpec
            feed = lambda b: jax.device_put(  # noqa: E731
                b, NamedSharding(mesh, PartitionSpec()))
        self.pool = [feed(b) for b in run.traffic.pool]
        losses = []
        grad_sq = change_sq = None
        for k in range(self.checked):
            m = self.trainer.step(self.pool[k], self.start[k], self.commit[k])
            losses.append(float(m["loss"]))
            if k == self.first_commit:
                grad_sq = np.asarray(self.norms.sq(
                    self.trainer.state.engine.g_bar))
        change_sq = np.asarray(self.norms.change_sq(self.trainer.state.params))
        self.readings = harness.readings(losses, grad_sq, change_sq)
        self.k = self.checked

    # ------------------------------------------------------------ window

    def window(self) -> dict:
        import jax
        run, cap, tr = self.run, self.run.capture, self.trainer
        K = len(self.pool)
        last = self.start.shape[0]
        done, failed = 0, 0
        stamps = []
        pending = collections.deque()
        read = 0
        with cap.trace():
            t0 = time.perf_counter()
            k = self.k
            while k < last:
                with cap.span("dispatch"):
                    pending.append(tr.step(self.pool[k % K], self.start[k],
                                           self.commit[k])["loss"])
                if len(pending) > self.ahead:
                    with cap.span("wait"):
                        failed += not math.isfinite(float(pending.popleft()))
                    read += 1
                k += 1
                done += 1
                stamps.append(time.perf_counter())
                # rounds still queued run at the pace of those read so far
                spent = stamps[-1] - t0
                if read and spent * (1 + len(pending) / read) >= run.seconds:
                    break
            with cap.span("wait"):
                while pending:
                    failed += not math.isfinite(float(pending.popleft()))
                jax.block_until_ready(tr.state)
            t1 = time.perf_counter()
        self.k = k
        gaps = np.diff([t0] + stamps)
        slow = int(np.argmax(gaps))
        print(f"[round] window: {done} rounds in {t1 - t0:.3f} s, median "
              f"period {np.median(gaps):.4f} s, longest {gaps[slow]:.4f} s "
              f"at round {slow}", flush=True)
        tokens = done * self.n * run.traffic.tokens_per_batch()
        return {"attempted": done, "failed": failed, "seconds": t1 - t0,
                "rounds": done, "tokens": tokens,
                "metrics": {"tokens_per_s": tokens / (t1 - t0)}}

    def free(self) -> None:
        import gc
        import jax
        for leaf in jax.tree.leaves(self.trainer.state):
            leaf.delete()
        self.trainer.state = None
        self.trainer = None
        self.pool = None
        gc.collect()

    # --------------------------------------------------------- reference

    def reference(self, precision: str = "f32", fault: str = "none") -> dict:
        """The plain reference over the checked steps' inputs: each round's
        loss (mean over the workers), the DuDe rule (a worker's job is
        latched at its start round and committed ``duration`` rounds later;
        ``g_bar`` is the mean of the workers' latest committed gradients)
        and SGD.  ``fault="half"`` takes every worker's mean over the first
        half of its positions only."""
        import jax
        import jax.numpy as jnp
        run = self.run
        cfg, ref, prog = run.cfg, run.ref, run.prog
        S = run.mix["seq_len"]
        keep = None
        if fault == "half":
            keep = jnp.arange(S) < S // 2

        def one(w, t, l):
            return ref.loss(w, t, l, cfg, precision, keep)

        def batch_loss(w, b):   # b leaves: [batch, S]
            return jnp.mean(jax.vmap(lambda t, l: one(w, t, l))(
                b["tokens"], b["labels"]))

        with jax.default_matmul_precision("highest"):
            loss_j = jax.jit(batch_loss)
            grad_j = jax.jit(jax.grad(batch_loss))
            sgd = jax.jit(lambda w, g: jax.tree.map(
                lambda a, b: a - run.lr * b, w, g))
            p0 = jax.jit(lambda k: ref.init(k, cfg))(harness.weight_key(run))
            hist = {0: p0}
            inflight, committed = {}, {}
            g_bar = None
            losses, grad_sq = [], None
            pool = run.traffic.pool
            for r in range(self.checked):
                p = hist[r]
                rows = [{k: jnp.asarray(v[i]) for k, v in pool[r].items()}
                        for i in range(self.n)]
                losses.append(float(np.mean([float(loss_j(p, b))
                                             for b in rows])))
                for i in np.flatnonzero(self.commit[r]):
                    committed[i] = inflight[i]
                for i in np.flatnonzero(self.start[r]):
                    inflight[i] = r
                if self.commit[r].any():
                    g_bar = None
                    for i, s in sorted(committed.items()):
                        b = {k: jnp.asarray(v[i]) for k, v in pool[s].items()}
                        g = grad_j(hist[s], b)
                        g_bar = g if g_bar is None else jax.tree.map(
                            jnp.add, g_bar, g)
                    g_bar = jax.tree.map(lambda x: x / self.n, g_bar)
                if r == self.first_commit:
                    grad_sq = harness.tree_leaf_sq(prog.to_program(g_bar))
                hist[r + 1] = p if g_bar is None else sgd(p, g_bar)
                needed = set(inflight.values()) | set(committed.values())
                for v in [v for v in hist if v not in needed and v < r + 1
                          and v != 0]:
                    del hist[v]
            change = jax.tree.map(jnp.subtract, hist[self.checked], p0)
            change_sq = harness.tree_leaf_sq(prog.to_program(change))
        return harness.readings(losses, grad_sq, change_sq)
