"""AsyncRunner: per-arrival training on the flat engine state.

The production counterpart of the event-driven simulator: the same arrival
semantics (``runtime/loop.py``) driving the paper's fully-asynchronous
server iteration on the canonical ``FlatTrainState`` — per arrival, one
``DuDeEngine.commit`` (or an ``AsyncAlgo`` rule from ``core/algos.py``) plus
the flat optimizer apply, compiled as ONE jitted device step that is
elementwise on the P-axis-sharded ``[P]`` slabs (mesh-native engines commit
under their ``shard_map``, so a sharded arrival step moves zero bytes).

Differences from the simulator, by design:

* math runs on flat slabs (identical values: flat and pytree applies agree
  bit-for-bit on f32 params, so a runner replaying a simulator trace
  reproduces its parameters exactly — ``tests/test_runtime.py``);
* the host never blocks per arrival: device steps are pushed through a
  bounded ``DeviceQueue`` (depth 2 = double buffering) that only waits when
  the device is ``queue_depth`` full steps behind the scheduler, and the
  loss EMA stays on device between record points;
* worker model snapshots are flat ``[P]`` vectors (n of them — the price of
  physical staleness), handed out by the loop's ``deliver`` hook.  The
  arrival step therefore donates the server slabs but NOT
  ``state.params``: the freshest snapshot aliases it.  Under a compressed
  ``commit_format`` the n snapshots are delta-encoded (tiled int8,
  ``core/compression.py``) against the run-start master instead of stored
  as full copies — ~3.9x less snapshot memory; commits themselves are
  compressed inside ``DuDeEngine.commit`` (int8 payload + per-tile scales
  + EF residual).

The per-arrival math lives in ``_RunSession`` — one object exposing the
``on_arrival`` / ``deliver`` callbacks ``drive_arrivals`` wants, plus the
``commit`` / ``snapshot_arrays`` halves the multi-host ``HostRunner``
(``runtime/hostloop.py``) drives off socket readiness — so the simulated
and the distributed run execute the IDENTICAL commit/apply/record path and
a recorded multi-host trace replays bit-for-bit through ``run()``.

Two gradient keying modes (``key_mode``):

* ``"arrival"`` (default, historical) — one global PRNG key split per
  arrival and one shared sampling rng, consumed in arrival order.  Only a
  simulator can do this: the key a gradient uses depends on WHEN it will
  arrive.
* ``"worker"`` — dispatch-deterministic: job ``j`` of worker ``w`` uses
  ``fold_in(fold_in(key(seed), w), j)`` and a per-worker
  ``np.random.SeedSequence([seed, w])`` sampling stream
  (:func:`worker_rng`).  A physically distributed worker can compute this
  WITHOUT knowing the global arrival order, so multi-host runs use it — and
  a replay with the same mode reproduces every gradient bitwise.

Documented in docs/async.md ("The AsyncRunner" / "In-flight depth and the
device queue" / "Multi-host transport").
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.algos import AsyncAlgo, make_async_algo
from ..core.compression import commit_digest
from ..core.engine import DuDeEngine
from ..optim import FlatOptState, FlatTrainState, flat_twin
from . import spans
from .arrivals import ArrivalProcess, ArrivalTrace
from .loop import LoopStats, drive_arrivals

Pytree = Any

__all__ = ["AsyncResult", "DeviceQueue", "AsyncRunner", "KEY_MODES",
           "worker_rng", "worker_key"]

KEY_MODES = ("arrival", "worker")


def worker_rng(seed: int, worker: int) -> np.random.Generator:
    """The per-worker sampling stream of ``key_mode="worker"`` runs — one
    ``SeedSequence([seed, worker])`` generator per worker, constructible
    identically on the server (replay) and on a remote worker process."""
    return np.random.default_rng(np.random.SeedSequence([seed, worker]))


def worker_key(seed: int, worker: int, job: int) -> jax.Array:
    """The gradient PRNG key of worker ``worker``'s ``job``-th dispatch
    under ``key_mode="worker"`` — pure fold_ins, no global split order."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), worker), job)


class DeviceQueue:
    """Bounded queue of in-flight device computations.

    ``push(x)`` enqueues a device value the host does not need yet; once
    more than ``depth`` values are outstanding the oldest is waited on —
    so the host runs at most ``depth`` steps ahead of the device (depth 2 =
    classic double buffering: one step executing, one queued behind it)
    while never synchronizing when a buffer slot is free.
    """

    def __init__(self, depth: int = 2):
        if depth < 1:
            raise ValueError(f"queue depth {depth} must be >= 1")
        self.depth = depth
        self._q: collections.deque = collections.deque()
        self.waits = 0  # times the host blocked: AsyncResult.queue_waits

    def push(self, value) -> None:
        self._q.append(value)
        if len(self._q) > self.depth:
            self.waits += 1
            with spans.span(spans.QUEUE_WAIT):
                jax.block_until_ready(self._q.popleft())

    def flush(self) -> None:
        while self._q:
            jax.block_until_ready(self._q.popleft())

    def __len__(self) -> int:
        return len(self._q)


@dataclasses.dataclass
class AsyncResult:
    """One AsyncRunner run, mirror of the simulator's ``SimResult`` plus the
    loop's scheduling stats and the recorded trace."""

    name: str
    times: np.ndarray        # simulated clock at each record point
    iters: np.ndarray        # server iterations at each record point
    losses: np.ndarray       # running train-loss EMA (or eval_fn) at records
    gnorms: np.ndarray       # |g| at each record point
    state: FlatTrainState    # final train state (flat)
    tau_max: int
    n_grads: int             # stochastic gradients computed
    stats: LoopStats
    # sparse commit transport (engines with sparse_meta): SparseRow commits
    # shipped host->device.  ``wire_bytes`` counts the FRAMED bytes a socket
    # would carry (prefix + header + manifest + padding — runtime/transport
    # framing; on multi-host runs, the bytes it actually carried);
    # ``payload_bytes`` the analytic array payload alone (0 on dense runs).
    wire_rows: int = 0
    wire_bytes: int = 0
    payload_bytes: int = 0
    # snapshot-encode cache: encodes actually run vs deliveries served from
    # the cache because params were unchanged since the last delivery
    snap_encodes: int = 0
    snap_reuses: int = 0
    # per-arrival commit digests (record_digests runs / multi-host runs)
    digests: Optional[tuple] = None
    # multi-host robustness counters (HostRunner runs; 0 on simulated runs)
    dropouts: int = 0
    reconnects: int = 0
    dropped_workers: tuple = ()
    # server-end socket byte totals of a hosted run (all frames: handshakes,
    # snapshots, commits, heartbeats), summed over every link ever attached
    wire_sent: int = 0
    wire_recv: int = 0
    # times the host blocked on the device queue (one ``dude.queue_wait``
    # span each): the device was ``queue_depth`` steps behind the loop
    queue_waits: int = 0

    @property
    def trace(self) -> ArrivalTrace:
        return self.stats.trace


class _RunSession:
    """The per-arrival math of ONE run, factored out of the event source.

    ``drive_arrivals`` consumes ``on_arrival`` / ``deliver``; the multi-host
    ``HostRunner`` calls ``commit`` (with a remotely computed gradient) and
    ``snapshot_arrays`` (the delta encoding a delivery ships) — all four run
    the same jits, counters and record points, so a simulated run, a hosted
    run, and a trace replay share one code path.
    """

    def __init__(self, runner: "AsyncRunner", state: FlatTrainState,
                 sample_fn: Optional[Callable], *, seed: int,
                 record_every: int, eval_fn: Optional[Callable], ema: float,
                 key_mode: str, record_digests: bool):
        if key_mode not in KEY_MODES:
            raise ValueError(
                f"unknown key_mode {key_mode!r}; options: {KEY_MODES}")
        r = self.r = runner
        n = runner.engine.n_workers
        self.sample_fn = sample_fn
        self.seed = seed
        self.record_every = record_every
        self.eval_fn = eval_fn
        self.ema = ema
        self.key_mode = key_mode
        self.state = state
        self.key = jax.random.PRNGKey(seed)
        self.rng = np.random.default_rng(seed)  # routing + "arrival" sampling
        self.rngs = ([worker_rng(seed, w) for w in range(n)]
                     if key_mode == "worker" else None)
        if key_mode == "worker" and r.algo.route is not None:
            raise ValueError(
                f"key_mode='worker' needs the greedy route (algo "
                f"{r.algo.name!r} routes {r.algo.route!r}): routed "
                "deliveries draw from a shared rng no remote worker can see")
        self.queue = DeviceQueue(r.queue_depth)
        self.running = None
        self.n_grads = 0
        self.wire_rows = 0
        self.wire_bytes = 0
        self.payload_bytes = 0
        self.snap_encodes = 0
        self.snap_reuses = 0
        self.arrived = [0] * n   # per-worker collected jobs (job id source)
        self.seq = -1            # the last arrival committed (span id)
        self.digests: Optional[list] = [] if record_digests else None
        self.times: list = []
        self.iters: list = []
        self.losses: list = []
        self.gnorms: list = []
        # deliver() cache: the params object the last snapshot encode ran
        # on, and its encoding.  Identity (`is`) comparison — the arrival
        # step returns a NEW params array whenever anything committed, so an
        # unchanged object means an unchanged snapshot; a delivery between
        # two commits (or before the first) reuses the last encode instead
        # of re-running it.  The object itself is held (not id()) so a GC'd
        # array can never alias a stale id.
        self._snap_cache = {"params": None, "enc": None}
        # every worker starts on the initial model (version 0)
        if r._compressed:
            # delta-encoded snapshots against the run-start master; the
            # zero delta (q=0 decodes to exactly 0) is ONE encode delivered
            # n ways — the first n cache reuses
            self.base = state.params
            zero_delta = r._snap_encode(self.base, self.base)
            self.snap_encodes = 1
            self.snap_reuses = n - 1
            self._snap_cache.update(params=self.base, enc=zero_delta)
            self.worker_snaps = [zero_delta for _ in range(n)]
            self.worker_params = None
        else:
            self.base = None
            self.worker_snaps = None
            self.worker_params = [state.params for _ in range(n)]
        if r._sparse:
            from .transport import (commit_frame_nbytes, pack_arrays,
                                    sparse_row_arrays)
            # the framed size of a commit depends only on (worker, job) ids
            # and the static SparseRow manifest — build the manifest once
            # from the row layout so per-arrival accounting never syncs the
            # device (and matches pack_arrays on a real row byte-for-byte)
            cap, k = r.engine.cap_tiles, r.engine.codec.topk
            self._row_manifest, _ = pack_arrays([
                np.zeros((cap,), np.int32), np.zeros((cap, k), np.uint8),
                np.zeros((cap, k), np.int8), np.zeros((cap,), np.float32),
                np.zeros((), np.int32)])
            self._commit_frame_nbytes = commit_frame_nbytes
            self._sparse_row_arrays = sparse_row_arrays

    # ------------------------------------------------------------ snapshots

    def worker_model(self, w: int) -> Pytree:
        r = self.r
        if r._sparse:
            return r._snap_unravel(self.base, self.worker_snaps[w])
        if r._compressed:
            q, s = self.worker_snaps[w]
            return r._snap_unravel(self.base, q, s)
        return r._unravel(self.worker_params[w])

    def deliver(self, worker: int) -> None:
        with spans.span(spans.DELIVER, arrival=self.seq):
            if not self.r._compressed:
                self.worker_params[worker] = self.state.params
                return
            params = self.state.params
            if self._snap_cache["params"] is not params:
                self._snap_cache["params"] = params
                self._snap_cache["enc"] = self.r._snap_encode(params,
                                                              self.base)
                self.snap_encodes += 1
            else:
                self.snap_reuses += 1
            self.worker_snaps[worker] = self._snap_cache["enc"]

    def snapshot_arrays(self, worker: int) -> tuple:
        """The host-side arrays a delivery ships on the wire: the full f32
        params (uncompressed formats) or the delta encoding vs the run-start
        base — EXACTLY what ``worker_model`` would decode, so a remote
        worker running the same ``_snap_unravel`` jit sees the same bits.
        Materializes to numpy (a send must); call after ``deliver``."""
        r = self.r
        if r._sparse:
            return self._sparse_row_arrays(self.worker_snaps[worker])
        if r._compressed:
            q, s = self.worker_snaps[worker]
            return (np.asarray(q), np.asarray(s))
        return (np.asarray(self.worker_params[worker]),)

    # -------------------------------------------------------------- commits

    def grad_for(self, view) -> tuple:
        """Local gradient compute (single-process path): the arriving
        worker's ``(loss, gflat)`` on the snapshot it holds, keyed per
        ``key_mode``."""
        w = view.worker
        worker_mode = self.key_mode == "worker"
        with spans.span(spans.SAMPLE):
            batch = self.sample_fn(w, self.rngs[w] if worker_mode
                                   else self.rng)
        with spans.span(spans.GRAD):
            if worker_mode:
                k1 = worker_key(self.seed, w, self.arrived[w])
            else:
                self.key, k1 = jax.random.split(self.key)
            loss, g = self.r._grad(self.worker_model(w), batch, k1)
            return loss, self.r._ravel(g)

    def commit(self, view, loss, gflat) -> bool:
        """One server iteration from an arrived gradient: encode/fold (or
        dense commit) + flat apply + EMA/record bookkeeping.  ``loss`` and
        ``gflat`` may be device values (local compute) or host arrays (a
        frame's payload) — the math is the same jit either way.  A partial
        arrival (client-state ``view.completeness`` < 1) scales the flat
        gradient BEFORE digesting/committing — the scale is an exact f32
        constant from the trace, and an elementwise f32 multiply commutes
        with ravel, so the simulator's pytree-side scaling stays bitwise
        identical."""
        r = self.r
        self.seq = int(view.seq)
        with spans.span(spans.COMMIT):
            w = int(view.worker)
            job = self.arrived[w]
            self.arrived[w] = job + 1
            self.n_grads += 1
            gflat = jnp.asarray(gflat)
            if view.completeness != 1.0:
                gflat = jnp.float32(view.completeness) * gflat
            if self.digests is not None:
                self.digests.append(commit_digest(np.asarray(gflat)))
            if r._sparse:
                st = self.state
                srv, wire = r._encode(st.engine, jnp.int32(w), gflat)
                self.wire_rows += 1
                nbytes = r._wire_nbytes(wire)
                self.payload_bytes += nbytes
                self.wire_bytes += self._commit_frame_nbytes(
                    w, job, self._row_manifest, nbytes)
                self.state = r._step_sparse(
                    FlatTrainState(st.params, st.opt, srv), jnp.int32(w),
                    wire)
            else:
                st = self.state
                self.state = r._step(st.params, st.opt, st.engine,
                                     jnp.int32(w), gflat, jnp.int32(view.tau))
            # device-side EMA; the queue keeps the host <= depth steps
            # ahead (the step counter comes out of the arrival step, so
            # waiting on it bounds the whole grad+commit+apply chain of that
            # arrival without holding a [P] output alive)
            loss = jnp.asarray(loss, jnp.float32)
            rn = self.running
            self.running = (loss if rn is None
                            else self.ema * rn + (1 - self.ema) * loss)
        self.queue.push((self.running, self.state.opt.step))
        it_after = view.iters + 1
        if it_after % self.record_every == 0:
            with spans.span(spans.RECORD):
                self.times.append(view.t)
                self.iters.append(it_after)
                if self.eval_fn is not None:
                    self.losses.append(float(self.eval_fn(
                        r.engine.spec.unravel(self.state.params))))
                else:
                    self.losses.append(float(self.running))
                # norm of the RAW arriving gradient — what SimResult records
                self.gnorms.append(float(jnp.sqrt(jnp.sum(
                    jnp.square(gflat)))))
        return True  # every async rule applies every arrival

    def on_arrival(self, view) -> bool:
        with spans.span(spans.ARRIVAL, arrival=int(view.seq),
                        worker=int(view.worker), tau=int(view.tau)):
            loss, gflat = self.grad_for(view)
            return self.commit(view, loss, gflat)

    # --------------------------------------------------------------- result

    def result(self, stats: LoopStats, **extra) -> AsyncResult:
        return AsyncResult(
            name=self.r.algo.name,
            times=np.asarray(self.times), iters=np.asarray(self.iters),
            losses=np.asarray(self.losses), gnorms=np.asarray(self.gnorms),
            state=self.state, tau_max=stats.tau_max,
            n_grads=self.n_grads, stats=stats,
            wire_rows=self.wire_rows, wire_bytes=self.wire_bytes,
            payload_bytes=self.payload_bytes,
            snap_encodes=self.snap_encodes, snap_reuses=self.snap_reuses,
            queue_waits=self.queue.waits,
            digests=None if self.digests is None else tuple(self.digests),
            **extra,
        )


class AsyncRunner:
    """Event-driven per-arrival training session over the flat engine.

    ``engine`` fixes the flat layout (and the mesh, when P-axis sharded);
    ``algo`` is an ``AsyncAlgo`` or a name from ``core.algos.ASYNC_ALGOS``;
    ``opt`` any optimizer with a flat twin.  ``grad_fn(params, batch, key)
    -> (loss, grads)`` computes one worker's stochastic gradient on the
    (stale) pytree params — the same callable contract as ``simulate`` —
    and is jitted once, so a runner and a simulator sharing one ``grad_fn``
    execute the identical compiled gradient.
    """

    def __init__(self, engine: DuDeEngine, algo, opt,
                 grad_fn: Callable[..., tuple], *,
                 queue_depth: int = 2,
                 max_in_flight: Optional[int] = None):
        self.engine = engine
        self.algo: AsyncAlgo = (make_async_algo(algo, engine)
                                if isinstance(algo, str) else algo)
        self.fopt = flat_twin(opt)
        self.max_in_flight = max_in_flight
        self.queue_depth = queue_depth
        spec = engine.spec
        # each jit is a named function under one device scope
        # (``runtime/spans.py``), so a profile names its module and ops
        self._grad = jax.jit(spans.scoped(spans.BACKWARD)(grad_fn))
        self._unravel = jax.jit(spans.scoped(spans.UNRAVEL)(spec.unravel))
        ravel_kw = {}
        if engine.mesh is not None:
            # land the raveled gradient straight in the engine's segment-
            # range P-axis layout, so commit's shard_map sees local shards
            from ..sharding import flat_vec_sharding
            ravel_kw["out_shardings"] = flat_vec_sharding(
                spec, engine.mesh, engine.paxes)

        @spans.scoped(spans.RAVEL)
        def ravel_grad(g):
            return spec.ravel(g, jnp.float32)

        self._ravel = jax.jit(ravel_grad, **ravel_kw)
        # the server slabs are donated (updated in place, not copied per
        # arrival); params are not: the freshest worker snapshot aliases
        # them.  The queue waits on the new opt step, so opt is kept too.
        self._step = jax.jit(self._arrival_step, donate_argnums=(2,))
        # Compressed commit formats also delta-encode the n worker model
        # snapshots against a fixed master base (run() start) instead of
        # keeping n full [P] f32 copies: snapshot w is stored as the tiled
        # int8 encoding of (master - base), reconstructed lazily at gradient
        # time.  Physical-staleness memory drops from 4nP to
        # ~nP(1 + 4/128) + 4P bytes.  The f32 format keeps the exact
        # aliasing path (trace replays stay bit-for-bit).
        codec = engine.codec
        self._compressed = codec.compressed
        # Sparse commit transport: when the engine carries touched-tile
        # metadata and the algo is the plain DuDe commit, the arrival step
        # splits into the sender encode (dense math, produces the O(k * cap)
        # SparseRow and advances EF) and the receiver fold (scatter-decode
        # straight into the slab) — the state crossing between them is the
        # wire row, whose bytes the run counts (AsyncResult.wire_bytes /
        # payload_bytes).
        self._sparse = engine.sparse_meta and self.algo.name == "dude"
        if self._sparse:
            from ..core.compression import sparse_wire_nbytes
            self._wire_nbytes = sparse_wire_nbytes
            self._encode = jax.jit(engine.encode_sparse_commit)

            def _fold_step(state, worker, row):
                with jax.named_scope(spans.COMMIT):
                    srv, g = engine.sparse_fold(state.engine, worker, row)
                with jax.named_scope(spans.APPLY):
                    t_new = state.opt.step + 1
                    pf, slots = self.fopt.update(state.params, g,
                                                 state.opt.slots, t_new)
                return FlatTrainState(pf, FlatOptState(t_new, slots), srv)

            self._step_sparse = jax.jit(_fold_step)
        if self._compressed:
            if self._sparse:
                # snapshots ride the same wire format (full tile capacity —
                # a whole-model delta touches most tiles); decode-identical
                # to the dense (q, scale) snapshot pair
                from ..core.compression import sparse_decode
                P = engine.P

                def snap_encode(params, base):
                    return codec.encode_sparse(
                        params.astype(jnp.float32) - base)

                @spans.scoped(spans.UNRAVEL)
                def snap_unravel(base, row):
                    return spec.unravel(base + sparse_decode(row, P))
            else:
                def snap_encode(params, base):
                    return codec.encode(params.astype(jnp.float32) - base)

                @spans.scoped(spans.UNRAVEL)
                def snap_unravel(base, q, s):
                    return spec.unravel(base + codec.decode(q, s))
            self._snap_encode = jax.jit(snap_encode)
            self._snap_unravel = jax.jit(snap_unravel)

    def _arrival_step(self, params, opt: FlatOptState, srv, worker, grad,
                      tau) -> FlatTrainState:
        """One server iteration: algo rule (commit for DuDe, s(τ)-damped
        commit for the staleness family) + flat apply, all elementwise on
        the (possibly P-sharded) slabs."""
        with jax.named_scope(spans.COMMIT):
            srv, g = self.algo.arrival(srv, worker, grad, tau)
        with jax.named_scope(spans.APPLY):
            t_new = opt.step + 1
            pf, slots = self.fopt.update(params, g, opt.slots, t_new)
        return FlatTrainState(pf, FlatOptState(t_new, slots), srv)

    # ------------------------------------------------------------- state

    def init_state(self, params: Pytree) -> FlatTrainState:
        """Fresh ``FlatTrainState`` (same construction as the Trainer's)."""
        from ..launch.steps import init_flat_train_state
        return init_flat_train_state(self.engine, self.fopt, params,
                                     algo=self.algo)

    def session(self, state: FlatTrainState,
                sample_fn: Optional[Callable] = None, *, seed: int = 0,
                record_every: int = 10, eval_fn: Optional[Callable] = None,
                ema: float = 0.9, key_mode: str = "arrival",
                record_digests: bool = False) -> _RunSession:
        """The per-arrival math session ``run`` drives — exposed so the
        multi-host ``HostRunner`` can drive the identical path from socket
        readiness (``sample_fn`` may be None when gradients arrive remotely
        and ``grad_for`` is never called)."""
        return _RunSession(self, state, sample_fn, seed=seed,
                           record_every=record_every, eval_fn=eval_fn,
                           ema=ema, key_mode=key_mode,
                           record_digests=record_digests)

    # --------------------------------------------------------------- run

    def run(
        self,
        process: ArrivalProcess,
        total_iters: int,
        sample_fn: Callable,
        state: FlatTrainState,
        *,
        seed: int = 0,
        record_every: int = 10,
        eval_fn: Optional[Callable] = None,
        ema: float = 0.9,
        max_time: Optional[float] = None,
        key_mode: str = "arrival",
        record_digests: bool = False,
    ) -> AsyncResult:
        """Drive ``total_iters`` per-arrival server iterations.

        ``sample_fn(worker, rng) -> batch`` draws from that worker's local
        data; ``seed`` feeds both the host rng (sampling + routing draws)
        and the gradient PRNG key — pass the seed a ``simulate`` run used
        and a trace replay reproduces its parameters bit-for-bit.  With
        ``key_mode="worker"`` the keys and sampling streams are
        dispatch-deterministic per worker (the multi-host convention — use
        it to replay a ``HostRunner`` trace); ``record_digests`` stamps
        every arrival's gradient (``AsyncResult.digests``) for comparison
        against a recorded multi-host run.  ``state``'s server slabs are
        donated to the first arrival step: use the result's state after.
        """
        n = self.engine.n_workers
        if process.n != n:
            raise ValueError(
                f"process has n={process.n}, engine n_workers={n}")
        sess = self.session(state, sample_fn, seed=seed,
                            record_every=record_every, eval_fn=eval_fn,
                            ema=ema, key_mode=key_mode,
                            record_digests=record_digests)
        try:
            stats = drive_arrivals(
                process, total_iters, sess.on_arrival, sess.deliver,
                route=self.algo.route, rng=sess.rng,
                max_in_flight=self.max_in_flight, max_time=max_time)
        finally:
            # a crashed arrival callback must not leave in-flight device
            # values dangling — flush the queue on every exit path
            sess.queue.flush()
        return sess.result(stats)
