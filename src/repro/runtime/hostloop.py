"""Multi-host event loop: the server iteration driven by socket readiness.

``runtime/loop.py`` drives per-arrival training off a SIMULATED clock; this
module drives the identical per-arrival math (``AsyncRunner``'s
``_RunSession``) off a REAL one: worker processes compute gradients on the
model snapshots the server ships them and push commits over the framed
transport (``runtime/transport.py``); the server folds each commit the
instant its frame arrives.  DuDe-ASGD's dual-delayed fold is what makes
this correct under any physical delay distribution — the server math never
assumes anything about WHEN a gradient arrives, only which model version
produced it (AsGrad's framing: the algorithm is distinguished by its
arrival process, which here is finally a real wire).

Protocol (all frames are ``runtime/transport.py`` frames)::

    worker -> server   hello     {workers: [ids]}            handshake
    server -> worker   welcome   {n, P, fmt, tile, topk, cap, axis, seed,
                                  key_mode} + [base f32 [P]]
    server -> worker   snapshot  {w, j, it} + delta payload  dispatch job j
    worker -> server   commit    {w, j, loss, dg} + [gflat f32 [P]]
    either -> either   ping / pong                           heartbeat
    server -> worker   bye                                   run finished

Determinism contract (the replay oracle): the server runs its session with
``key_mode="worker"``, so job ``j`` of worker ``w`` is keyed
``fold_in(fold_in(key(seed), w), j)`` and sampled from the per-worker
``SeedSequence([seed, w])`` stream — quantities a remote process computes
without global knowledge.  Each live arrival gets the canonical trace
stamps ``t_arrive = seq + 1`` and ``t_dispatch = previous arrival-of-w's
t_arrive`` (0 for the first), which is exactly the event evolution
``drive_arrivals`` reconstructs under greedy routing — so replaying the
recorded ``ArrivalTrace`` through the single-process ``AsyncRunner`` with
``key_mode="worker"`` recomputes every gradient, every fold, and the final
``[P]`` params BIT-FOR-BIT (and the per-arrival digests localize any
divergence).  ``tests/test_transport.py`` asserts this end to end.

Failure semantics:

* every recv carries a deadline; links that stay silent past
  ``heartbeat_s`` get a PING, past ``dead_after_s`` are declared dead;
* EOF (``TransportClosed``) is an immediate dropout: the link's logical
  workers stop arriving, counted in ``AsyncResult.dropouts`` /
  ``dropped_workers``; the run CONTINUES on the surviving links (greedy
  routing never blocks on a dead worker);
* a reconnecting process re-handshakes through ``accept_fn``; each of its
  logical workers is re-sent the EXACT snapshot it held when it died (the
  session keeps per-worker snapshots) plus its in-flight job index, so the
  retried job computes the gradient the replay expects and tau bookkeeping
  continues unbroken.

Documented in docs/async.md ("Multi-host transport").
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from ..core.compression import CommitCodec, commit_digest, sparse_decode
from .arrivals import Arrival, ArrivalTrace
from .loop import ArrivalView, LoopStats
from .runner import AsyncResult, AsyncRunner, worker_key, worker_rng
from .transport import (SocketTransport, TransportClosed, TransportError,
                        TransportTimeout, commit_header,
                        sparse_row_from_arrays)

__all__ = ["HostRunner", "run_worker", "accept_links", "poll_accept_fn"]


# --------------------------------------------------------------- server side

def accept_links(listener, n_links: int, *, timeout: float = 60.0,
                 transport_timeout: float = 30.0) -> list:
    """Accept ``n_links`` connections off a ``serve_listener`` socket."""
    import socket as _socket
    out: list = []
    deadline = time.monotonic() + timeout
    while len(out) < n_links:
        try:
            sock, _ = listener.accept()
            out.append(SocketTransport(sock, timeout=transport_timeout))
        except (BlockingIOError, InterruptedError, _socket.timeout):
            if time.monotonic() > deadline:
                raise TransportTimeout(
                    f"only {len(out)}/{n_links} links connected "
                    f"within {timeout:.0f}s") from None
            time.sleep(0.02)
    return out


def poll_accept_fn(listener, *, transport_timeout: float = 30.0) -> Callable:
    """Non-blocking accept poll for mid-run reconnects (``accept_fn``)."""
    def accept():
        try:
            sock, _ = listener.accept()
            return SocketTransport(sock, timeout=transport_timeout)
        except OSError:
            return None
    return accept


class _Link:
    """One connected worker process: a transport + its logical worker ids."""

    def __init__(self, transport, workers: tuple):
        self.t = transport
        self.workers = workers
        now = time.monotonic()
        self.last_heard = now
        self.last_ping = now


class HostRunner:
    """The multi-host twin of ``AsyncRunner.run``: same session math, real
    arrivals.

    ``runner`` supplies the engine/algo/optimizer jits (gradients are NOT
    computed here — they arrive in commit frames); the transport policy
    knobs bound how long a silent link lives.  ``serve`` is the entry
    point; it returns the same ``AsyncResult`` a simulated run would, with
    the robustness counters filled in.
    """

    def __init__(self, runner: AsyncRunner, *, heartbeat_s: float = 5.0,
                 dead_after_s: float = 20.0, poll_s: float = 0.05,
                 hello_timeout_s: float = 30.0, allow_reconnect: bool = True):
        if dead_after_s <= heartbeat_s:
            raise ValueError(
                f"dead_after_s={dead_after_s} must exceed "
                f"heartbeat_s={heartbeat_s} (a PING needs time to answer)")
        if runner.algo.route is not None:
            raise ValueError(
                "multi-host serving needs the greedy route (route=None); "
                f"algo {runner.algo.name!r} routes {runner.algo.route!r}")
        self.runner = runner
        self.heartbeat_s = heartbeat_s
        self.dead_after_s = dead_after_s
        self.poll_s = poll_s
        self.hello_timeout_s = hello_timeout_s
        self.allow_reconnect = allow_reconnect

    # ------------------------------------------------------------ handshake

    def _welcome_meta(self, seed: int) -> dict:
        eng = self.runner.engine
        codec: CommitCodec = eng.codec
        return {
            "n": eng.n_workers, "P": eng.P, "fmt": codec.format,
            "tile": codec.tile, "topk": codec.topk,
            "cap": eng.cap_tiles if eng.sparse_meta else 0,
            "axis": eng.axis_size, "seed": int(seed), "key_mode": "worker",
        }

    def _handshake(self, transport, claimed: set, n: int) -> tuple:
        msg = transport.recv(timeout=self.hello_timeout_s)
        if msg.kind != "hello":
            raise TransportError(
                f"expected hello, got {msg.kind!r} (bad client?)")
        workers = tuple(int(w) for w in msg.meta.get("workers", ()))
        if not workers:
            raise TransportError("hello claims no workers")
        for w in workers:
            if not 0 <= w < n:
                raise TransportError(
                    f"hello claims worker {w}, engine has n={n}")
            if w in claimed:
                raise TransportError(
                    f"worker {w} is already attached to a live link")
        return workers

    # ---------------------------------------------------------------- serve

    def serve(self, links: Sequence, total_iters: int, state, *,
              seed: int = 0, record_every: int = 10,
              eval_fn: Optional[Callable] = None, ema: float = 0.9,
              accept_fn: Optional[Callable] = None,
              checkpoint_every: Optional[int] = None,
              checkpoint_fn: Optional[Callable] = None,
              max_wall_s: Optional[float] = None) -> AsyncResult:
        """Drive ``total_iters`` server iterations from live commit frames.

        ``links`` are connected transports that have NOT yet said hello
        (``accept_links`` output); their hellos must claim every engine
        worker exactly once.  ``accept_fn`` (optional, e.g.
        ``poll_accept_fn``) is polled for reconnecting processes.
        ``checkpoint_fn(state, it)`` fires every ``checkpoint_every``
        applied iterations — mid-run server-side checkpointing, which the
        single-process runner's round-cadence hooks cannot do.
        """
        runner = self.runner
        n = runner.engine.n_workers
        sess = runner.session(state, None, seed=seed,
                              record_every=record_every, eval_fn=eval_fn,
                              ema=ema, key_mode="worker",
                              record_digests=True)
        base_np = np.asarray(sess.base if sess.base is not None
                             else state.params, np.float32)
        welcome = self._welcome_meta(seed)

        live: list = []
        all_links: list = []   # every transport ever attached (byte totals)
        worker_link: dict = {}
        dropped: set = set()
        never_attached = set(range(n))
        version_iter = [0] * n
        last_arrive = [0.0] * n
        arrivals: list = []
        it = 0
        seq = 0
        tau_max = 0
        inflight_max = 0
        dropouts = 0
        reconnects = 0
        t_start = time.monotonic()

        def attach(transport, *, rejoin: bool) -> None:
            nonlocal inflight_max, reconnects
            workers = self._handshake(transport, set(worker_link), n)
            if rejoin:
                for w in workers:
                    if w in dropped or w in never_attached:
                        continue
                    raise TransportError(
                        f"worker {w} reconnecting but was never dropped")
            link = _Link(transport, workers)
            transport.send("welcome", welcome, [base_np])
            for w in workers:
                worker_link[w] = link
                if w in dropped:  # true rejoin (not a late first join)
                    reconnects += 1
                dropped.discard(w)
                never_attached.discard(w)
                # dispatch: job = collected commits of w (a lost in-flight
                # job is RETRIED at the same index); payload = the snapshot
                # w held at its last delivery — what the replay's gradient
                # for this job will be computed on
                transport.send("snapshot",
                               {"w": w, "j": sess.arrived[w], "it": it},
                               sess.snapshot_arrays(w))
            live.append(link)
            all_links.append(transport)
            inflight_max = max(inflight_max, len(worker_link))

        def drop(link, reason: str) -> None:
            nonlocal dropouts
            if link not in live:
                return
            live.remove(link)
            for w in link.workers:
                if worker_link.get(w) is link:
                    del worker_link[w]
                    dropped.add(w)
                    dropouts += 1
            try:
                link.t.close()
            except Exception:
                pass

        def handle(link, msg) -> bool:
            """Process one frame; True iff it applied a server iteration."""
            nonlocal it, seq, tau_max
            if msg.kind == "ping":
                link.t.send("pong")
                return False
            if msg.kind in ("pong", "busy"):
                return False
            if msg.kind == "bye":
                drop(link, "client said bye")
                return False
            if msg.kind != "commit":
                raise TransportError(
                    f"unexpected {msg.kind!r} frame on an attached link")
            w, j = int(msg.meta["w"]), int(msg.meta["j"])
            if worker_link.get(w) is not link:
                raise TransportError(
                    f"commit for worker {w} from a link that does not "
                    f"own it")
            if j < sess.arrived[w]:
                return False  # duplicate from a link presumed dead — drop
            if j > sess.arrived[w]:
                raise TransportError(
                    f"worker {w} commits job {j}, server expected "
                    f"{sess.arrived[w]} (protocol desync)")
            (gflat,) = msg.arrays
            dg = commit_digest(gflat)
            if msg.meta.get("dg", dg) != dg:
                raise TransportError(
                    f"commit digest mismatch for worker {w} job {j}: "
                    f"frame says {msg.meta['dg']}, payload hashes to {dg} "
                    "(corrupt frame or diverged worker)")
            t_arr = float(seq + 1)
            tau = it + 1 - version_iter[w]
            tau_max = max(tau_max, tau)
            arrivals.append(Arrival(seq, w, last_arrive[w], t_arr))
            last_arrive[w] = t_arr
            sess.commit(ArrivalView(seq, w, t_arr, tau, it),
                        float(msg.meta["loss"]), gflat)
            seq += 1
            it += 1
            if checkpoint_fn is not None and checkpoint_every and \
                    it % checkpoint_every == 0:
                checkpoint_fn(sess.state, it)
            if it < total_iters:
                # greedy delivery: the arriving worker restarts on the
                # freshest model (same bookkeeping as drive_arrivals)
                sess.deliver(w)
                version_iter[w] = it
                link.t.send("snapshot", {"w": w, "j": sess.arrived[w],
                                         "it": it},
                            sess.snapshot_arrays(w))
            return True

        try:
            for transport in links:
                attach(transport, rejoin=False)
            if worker_link and set(range(n)) - set(worker_link):
                missing = sorted(set(range(n)) - set(worker_link))
                raise TransportError(
                    f"initial links leave workers {missing} unattached — "
                    "every engine worker needs exactly one link")

            while it < total_iters:
                if max_wall_s is not None and \
                        time.monotonic() - t_start > max_wall_s:
                    break
                if accept_fn is not None and self.allow_reconnect and \
                        (dropped or never_attached):
                    fresh = accept_fn()
                    if fresh is not None:
                        try:
                            attach(fresh, rejoin=True)
                        except (TransportError, TransportTimeout):
                            fresh.close()
                if not live:
                    if accept_fn is None or not self.allow_reconnect:
                        break  # nobody left and nobody can come back
                    time.sleep(self.poll_s)
                    continue
                def pump(link, timeout) -> bool:
                    """Read + handle at most one frame off ``link``;
                    True iff a frame was processed."""
                    try:
                        msg = link.t.recv(timeout=timeout)
                    except TransportTimeout:
                        return False
                    except TransportClosed:
                        drop(link, "EOF")
                        return False
                    link.last_heard = time.monotonic()
                    try:
                        handle(link, msg)
                    except TransportClosed:
                        drop(link, "send failed")
                    return True

                # single link: block the full poll; several: short slices
                per_recv = self.poll_s if len(live) == 1 else 0.002
                for link in list(live):
                    if it >= total_iters:
                        break
                    if pump(link, per_recv):
                        # drain the backlog that queued up while the fold
                        # ran — heartbeats trapped behind a slow commit
                        # must reach last_heard before the death check
                        while link in live and it < total_iters and \
                                pump(link, 0.001):
                            pass
                # heartbeat maintenance runs EVERY pass (not just idle
                # ones): when surviving links saturate the server with
                # commits, a silent link must still age out on schedule —
                # the last_heard age test keeps busy links unpinged
                for link in list(live):
                    silent = time.monotonic() - link.last_heard
                    if silent > self.dead_after_s:
                        # one last-chance read: a link whose frames are
                        # waiting unread (the reader was starved by long
                        # folds) is not dead, just unheard
                        if pump(link, 0.001):
                            continue
                        drop(link, f"silent {silent:.1f}s (heartbeat)")
                    elif silent > self.heartbeat_s and \
                            time.monotonic() - link.last_ping > \
                            self.heartbeat_s:
                        link.last_ping = time.monotonic()
                        try:
                            link.t.send("ping")
                        except (TransportClosed, TransportTimeout):
                            drop(link, "ping failed")
        finally:
            for link in list(live):
                try:
                    link.t.send("bye")
                except (TransportError, OSError):
                    pass
            # linger on normal completion: a worker mid-compute when the
            # run finished will still push one last (discarded) commit
            # before it reads the BYE — keep its link readable so that
            # send succeeds and it exits cleanly instead of on EOF
            if it >= total_iters:
                deadline = time.monotonic() + 2.0
                while live and time.monotonic() < deadline:
                    for link in list(live):
                        try:
                            msg = link.t.recv(timeout=0.02)
                            if msg.kind == "bye":
                                raise TransportClosed("client left")
                        except TransportTimeout:
                            pass
                        except (TransportClosed, TransportError):
                            live.remove(link)
                            try:
                                link.t.close()
                            except Exception:
                                pass
            for link in list(live):
                try:
                    link.t.close()
                except Exception:
                    pass
            sess.queue.flush()

        trace = ArrivalTrace.from_arrivals(n, arrivals, digests=sess.digests)
        stats = LoopStats(arrivals=seq, iters=it, tau_max=tau_max,
                          t_end=float(seq), max_in_flight=inflight_max,
                          trace=trace)
        res = sess.result(stats)
        # socket totals for the server end (handshakes + snapshots +
        # commits, framed) over every link that ever attached; the
        # session's commit-row accounting stays in wire_rows/payload_bytes
        res.wire_sent = sum(t.wire_sent for t in all_links)
        res.wire_recv = sum(t.wire_recv for t in all_links)
        res.dropouts = dropouts
        res.reconnects = reconnects
        res.dropped_workers = tuple(sorted(dropped))
        return res


# --------------------------------------------------------------- client side

class _Bye(Exception):
    pass


def run_worker(transport_factory: Callable, workers: Sequence[int],
               grad_fn: Callable, sample_fn: Callable, spec, *,
               poll_s: float = 0.2, heartbeat_s: float = 5.0,
               max_reconnects: int = 0,
               reconnect_backoff_s: float = 0.5) -> dict:
    """One worker process: serve ``workers``' gradient jobs until BYE.

    ``transport_factory() -> transport`` dials the server (called again on
    reconnect, up to ``max_reconnects`` times after a drop);
    ``grad_fn(params, batch, key) -> (loss, grads)`` and ``sample_fn(w,
    rng) -> batch`` are the SAME callables a single-process run would use;
    ``spec`` the engine's ``FlatSpec`` (built locally from the model
    config — validated against the server's WELCOME).  Snapshot decode and
    gradient ravel run the same jitted expressions as the server's replay,
    so the committed bytes are bit-identical to what the replay recomputes.

    Sampling streams survive reconnects: job indices the server re-issues
    reuse the cached last batch, skipped-ahead indices fast-forward the
    per-worker rng — so a resumed worker stays aligned with the replay's
    draw order.  Returns ``{"commits", "reconnects", "wire_sent",
    "wire_recv"}``.
    """
    import jax.numpy as jnp

    workers = tuple(int(w) for w in workers)
    commits = 0
    reconnects = 0
    wire_sent = 0
    wire_recv = 0
    jits: dict = {}
    rngs: dict = {}
    drawn = {w: 0 for w in workers}
    last_batch: dict = {}

    def build(meta, base_np):
        """Per-run jits, built once from the first WELCOME."""
        P = int(meta["P"])
        if spec.padded_size != P:
            raise TransportError(
                f"local FlatSpec has P={spec.padded_size}, server says {P} "
                "— model config or mesh axis size mismatch")
        fmt = meta["fmt"]
        codec = CommitCodec(format=fmt, tile=int(meta["tile"]),
                            topk=int(meta["topk"]))
        base = jnp.asarray(base_np)
        # the runner's _snap_unravel/_unravel/_ravel jits, less their
        # profiler scopes (metadata only) -> bit-identical reconstruction
        if fmt == "topk_ef":
            unsnap = jax.jit(lambda row: spec.unravel(
                base + sparse_decode(row, P)))

            def decode(arrays):
                return unsnap(sparse_row_from_arrays(arrays))
        elif codec.compressed:
            unsnap = jax.jit(lambda q, s: spec.unravel(
                base + codec.decode(q, s)))

            def decode(arrays):
                return unsnap(*arrays)
        else:
            unsnap = jax.jit(spec.unravel)

            def decode(arrays):
                return unsnap(arrays[0])
        jits["decode"] = decode
        jits["grad"] = jax.jit(grad_fn)
        jits["ravel"] = jax.jit(lambda g: spec.ravel(g, jnp.float32))
        jits["seed"] = int(meta["seed"])
        for w in workers:
            rngs.setdefault(w, worker_rng(jits["seed"], w))

    def batch_for(w, j):
        if w in last_batch and last_batch[w][0] == j:
            return last_batch[w][1]  # server retried the in-flight job
        if j < drawn[w]:
            raise TransportError(
                f"worker {w} asked to rewind to job {j} "
                f"(already drew {drawn[w]} batches)")
        while drawn[w] < j:  # fresh process rejoining mid-run: fast-forward
            sample_fn(w, rngs[w])
            drawn[w] += 1
        batch = sample_fn(w, rngs[w])
        drawn[w] += 1
        last_batch[w] = (j, batch)
        return batch

    def session(transport):
        nonlocal commits
        pending: deque = deque()
        transport.send("hello", {"workers": list(workers)})
        msg = transport.recv(timeout=60.0)
        if msg.kind != "welcome":
            raise TransportError(f"expected welcome, got {msg.kind!r}")
        if not jits:
            build(msg.meta, msg.arrays[0])

        # heartbeat THREAD, not inline pings: a gradient compute (or the
        # first jit compile) can legitimately outlast the server's
        # dead_after_s, and the main thread cannot ping mid-compute — the
        # transport's send lock keeps ping frames out of commit streams
        stop_hb = threading.Event()

        def _heartbeat():
            while not stop_hb.wait(heartbeat_s):
                try:
                    transport.send("ping")
                except TransportError:
                    return

        hb = threading.Thread(target=_heartbeat, daemon=True)
        hb.start()

        def handle(msg):
            if msg.kind == "bye":
                raise _Bye
            if msg.kind == "ping":
                transport.send("pong")
            elif msg.kind == "snapshot":
                pending.append((int(msg.meta["w"]), int(msg.meta["j"]),
                                msg.arrays))
            # pong / anything else: heartbeat only

        try:
            while True:
                # drain frames; block only when there is no job to compute
                try:
                    while True:
                        msg = transport.recv(timeout=0.001 if pending
                                             else poll_s)
                        handle(msg)
                except TransportTimeout:
                    pass
                if not pending:
                    continue
                w, j, arrays = pending.popleft()
                params = jits["decode"](arrays)
                key = worker_key(jits["seed"], w, j)
                loss, g = jits["grad"](params, batch_for(w, j), key)
                gflat = np.asarray(jits["ravel"](g), np.float32)
                transport.send("commit",
                               commit_header(w, j, float(loss),
                                             commit_digest(gflat)),
                               [gflat])
                commits += 1
        finally:
            stop_hb.set()

    attempts = 0
    while True:
        transport = transport_factory()
        try:
            session(transport)
        except _Bye:
            try:
                transport.send("bye")
            except TransportError:
                pass
            wire_sent += transport.wire_sent
            wire_recv += transport.wire_recv
            transport.close()
            break
        except (TransportClosed, TransportTimeout):
            wire_sent += transport.wire_sent
            wire_recv += transport.wire_recv
            try:
                transport.close()
            except Exception:
                pass
            if attempts >= max_reconnects:
                raise
            attempts += 1
            reconnects += 1
            time.sleep(reconnect_backoff_s * attempts)
    return {"commits": commits, "reconnects": reconnects,
            "wire_sent": wire_sent, "wire_recv": wire_recv}
