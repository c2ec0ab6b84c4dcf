"""Arrival mode: ``Trainer.run_async``, one DuDe server iteration per
gradient arrival (the arriving worker's backward on the model it holds,
the ravel, the O(P) commit into ``g_bar`` and the SGD apply), driven by the
program's per-arrival loop on the fixed-speed arrival process.

The benchmark's ``sample_fn`` hands each worker its next batch from the
pool made in set-up, and stamps the host clock at every call: the loop
calls it once per arrival, so the stamps give the period of each server
iteration as the loop sees it.

Set-up runs the checked sessions (``run_async`` calls of 1 and 2 arrivals,
every loss recorded) and reads the recorded losses, the per-leaf norms of
``g_bar`` after the first arrival and of the parameters' change after the
last.  Then a warm-up call at the window's ``record_every`` fixes the
window's arrival count from its rate; the window is one ``run_async``
call on the same object.
"""

from __future__ import annotations

import heapq
import math
import time

import numpy as np

import harness
import traffic as gen

EMA = 0.9           # the program's default loss EMA at record points


class Mode:
    kind = "arrival"

    def __init__(self, run: harness.Run):
        self.run = run
        self.n = run.mix["n_workers"]
        self.sessions = list(run.mix["checked_sessions"])
        self.arrivals = gen.FixedArrivals(run.traffic.times)
        self.stamps: list = []
        self.feed = None        # host batch -> device batch (set-up)

    def sample_fn(self, worker, rng):
        """The j-th call for ``worker`` returns pool entry j (mod the pool)."""
        self.stamps.append(time.perf_counter())
        j = self.count[worker]
        self.count[worker] = j + 1
        with self.run.capture.span("sample"):
            return self.pool[j % len(self.pool)][worker]

    def _call(self, iters: int, record_every: int):
        return self.trainer.run_async(
            self.arrivals, iters, self.sample_fn, record_every=record_every,
            seed=self.run.keys["program"])

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        import jax
        from repro.api import Trainer
        run = self.run
        self.trainer = Trainer.create(harness.trainer_config(run, self.n),
                                      params=harness.program_weights(run))
        self.norms = harness.LeafNorms(run,
                                       int(self.trainer.state.params.shape[0]))
        feed = self.feed or jax.device_put
        self.pool = [[feed({k: v[i] for k, v in b.items()})
                      for i in range(self.n)] for b in run.traffic.pool]
        self.count = [0] * self.n
        losses, grad_sq = [], None
        for s, iters in enumerate(self.sessions):
            res = self._call(iters, 1)
            losses += [float(x) for x in res.losses]
            if s == 0:
                grad_sq = np.asarray(self.norms.sq(
                    self.trainer.state.engine.g_bar))
        change_sq = np.asarray(self.norms.change_sq(self.trainer.state.params))
        self.readings = harness.readings(losses, grad_sq, change_sq)
        # warm the window's call (its record points included) and time it
        every = run.mix["record_every"]
        warm = run.mix["warm_arrivals"]
        self.stamps = []
        res = self._call(warm, every)
        jax.block_until_ready(res.state)
        t = self.stamps
        rate = (warm // 2) / (t[-1] - t[warm - 1 - warm // 2])
        self.iters = max(every, int(round(rate * run.seconds)))
        print(f"[arrival] warm-up: {rate:.3f} arrivals/s, tau_max "
              f"{res.tau_max} -> window of {self.iters} arrivals",
              flush=True)

    # ------------------------------------------------------------ window

    def window(self) -> dict:
        import jax
        run, cap = self.run, self.run.capture
        every = run.mix["record_every"]
        with cap.trace():
            self.stamps = []
            t0 = time.perf_counter()
            with cap.span("run_async"):
                res = self._call(self.iters, every)
            with cap.span("wait"):
                jax.block_until_ready(res.state)
            t1 = time.perf_counter()
        done = int(res.stats.iters)
        gaps = np.diff(np.asarray([t0] + self.stamps)) * 1e3
        failed = int(sum(not math.isfinite(float(x)) for x in res.losses))
        slow = int(np.argmax(gaps))
        print(f"[arrival] window: {done} arrivals in {t1 - t0:.3f} s, "
              f"tau_max {res.tau_max}, records {len(res.losses)}, loss ema "
              f"{[round(float(x), 4) for x in res.losses[-3:]]}; periods: "
              f"median {np.median(gaps):.2f} ms, longest {gaps[slow]:.1f} "
              f"ms at arrival {slow}", flush=True)
        return {"attempted": done, "failed": failed, "seconds": t1 - t0,
                "arrivals": done,
                "tokens": done * run.traffic.tokens_per_batch(),
                "metrics": {"arrivals_per_s": done / (t1 - t0),
                            "arrival_ms_p95": float(np.percentile(gaps, 95))}}

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
        """The plain reference over the checked sessions: each session hands
        every worker the current model and starts all at time 0; worker i
        arrives every ``times[i]`` (ties to the lower index), its gradient
        taken on the model it was handed, then ``g_bar += (g - g_i) / n``,
        ``g_i = g``, an SGD step, and the worker gets the new model.  The
        recorded loss is the session's EMA of the arriving losses."""
        import jax
        import jax.numpy as jnp
        run = self.run
        cfg, ref, prog = run.cfg, run.ref, run.prog
        S = run.mix["seq_len"]
        keep = jnp.arange(S) < S // 2 if fault == "half" else None
        times = run.traffic.times

        def batch_loss(w, b):
            return jnp.mean(jax.vmap(
                lambda t, l: ref.loss(w, t, l, cfg, precision, keep))(
                b["tokens"], b["labels"]))

        with jax.default_matmul_precision("highest"):
            vg = jax.jit(jax.value_and_grad(batch_loss))
            sgd = jax.jit(lambda w, g: jax.tree.map(
                lambda a, b: a - run.lr * b, w, g))
            p0 = jax.jit(lambda k: ref.init(k, cfg))(harness.weight_key(run))
            p, g_bar, g_w = p0, None, {}
            count = [0] * self.n
            losses, grad_sq = [], None
            pool = run.traffic.pool
            for iters in self.sessions:
                held = {w: p for w in range(self.n)}
                heap = [(float(times[w]), w) for w in range(self.n)]
                heapq.heapify(heap)
                ema = None
                for _ in range(iters):
                    t, w = heapq.heappop(heap)
                    j = count[w]
                    count[w] += 1
                    b = {k: jnp.asarray(v[w])
                         for k, v in pool[j % len(pool)].items()}
                    loss, g = vg(held[w], b)
                    loss = float(loss)
                    ema = loss if ema is None else EMA * ema + (1 - EMA) * loss
                    losses.append(ema)
                    old = g_w.get(w)
                    delta = g if old is None else jax.tree.map(
                        jnp.subtract, g, old)
                    upd = jax.tree.map(lambda x: x / self.n, delta)
                    g_bar = upd if g_bar is None else jax.tree.map(
                        jnp.add, g_bar, upd)
                    g_w[w] = g
                    if grad_sq is None:
                        grad_sq = harness.tree_leaf_sq(prog.to_program(g_bar))
                    p = sgd(p, g_bar)
                    held[w] = p
                    heapq.heappush(heap, (t + float(times[w]), w))
            change = jax.tree.map(jnp.subtract, p, p0)
            change_sq = harness.tree_leaf_sq(prog.to_program(change))
        return harness.readings(losses, grad_sq, change_sq)
