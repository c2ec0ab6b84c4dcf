"""The ``Trainer`` session: one object, one train state, one step signature.

``Trainer.create(config)`` resolves a ``TrainerConfig`` into a live session:
model config, mesh-native ``DuDeEngine``, the ``RoundAlgo`` server rule, the
flat optimizer twin, and ONE canonical train state — a ``FlatTrainState``
whose master params, optimizer slots and server slabs all live in the
engine's segment-range ``[P]`` layout (P-axis sharded when a mesh is given).
Every round algorithm in the registry — ``dude``, ``dude_accum``, and the
round-based Table-1 baselines ``sync_sgd`` / ``mifa`` / ``fedbuff`` — runs
through the same jitted step:

    metrics = trainer.step(batch, start_mask, commit_mask)

and every ARRIVAL algorithm (``dude``, ``vanilla_asgd``, ``uniform_asgd``,
``shuffled_asgd``) through the event-driven async runtime on the same
state:

    result = trainer.run_async(arrivals, total_iters, sample_fn)

There is no flat/pytree fork, no per-algo state tuple, and no caller-side
restore dispatch: ``trainer.save(dir)`` always writes the flat format with
the spec segment table, and ``Trainer.restore(ckpt_dir, config)`` reads
EITHER a flat or a legacy pytree directory (``checkpoint_format`` decides),
so old checkpoints keep loading through the one entry point.

``TrainerConfig.params_layout`` picks how the step feeds the forward:
``"replicated"`` re-materializes the full ``[P]`` master vector each step
(the correctness oracle), ``"tp"`` routes the P-shards straight into the
params' Megatron-TP layout through the ``FlatSpec`` exchange ring, so no
device ever holds the whole vector (docs/engine.md, "TP-native unravel").

For lowering-only work (dry-run, HLO analysis) ``Trainer.abstract(config)``
builds the same session without materializing any state;
``trainer.input_specs(shape_name)`` returns the (shapes, shardings) of the
full step signature, and ``trainer.step_fn`` is the unjitted step for
custom ``jax.jit`` wrapping (shardings, donation).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..checkpoint import restore_train_state, save_checkpoint
from ..core.algos import (
    ASYNC_ALGOS, ROUND_ALGOS, AsyncAlgo, RoundAlgo, make_async_algo,
    make_round_algo,
)
from ..launch.steps import (
    abstract_train_state, init_flat_train_state, make_engine, make_train_step,
    train_batch_specs,
)
from ..models import lm_init
from ..optim import FlatTrainState, flat_twin
from ..runtime import spans
from .config import ConfigError, TrainerConfig

Pytree = Any

__all__ = ["Trainer"]


class Trainer:
    """A live training session over the single flat train state.

    Build with ``Trainer.create`` / ``Trainer.restore`` /
    ``Trainer.abstract`` — the bare constructor wires the session objects
    but does not initialize state.
    """

    def __init__(self, config: TrainerConfig):
        self.config = config
        self.cfg = config.model_config          # resolved ModelConfig
        self.opt = config.make_optimizer()
        self.fopt = flat_twin(self.opt)
        self.dude_cfg = config.dude_config
        self.options = config.train_options
        self.mesh = config.mesh
        self.engine = make_engine(self.cfg, self.mesh, self.dude_cfg,
                                  self.options)
        # one session may hold BOTH granularities of the same rule: a round
        # rule (trainer.step) and/or an arrival rule (trainer.run_async) —
        # ``dude`` has both, the ASGD disciplines are arrival-only,
        # dude_accum and the Table-1 round baselines are round-only.
        self.algo: Optional[RoundAlgo] = (
            make_round_algo(config.algo, self.engine,
                            buffer_size=config.fedbuff_buffer_size)
            if config.algo in ROUND_ALGOS else None)
        self.async_algo: Optional[AsyncAlgo] = (
            make_async_algo(config.algo, self.engine)
            if config.algo in ASYNC_ALGOS else None)
        self.state: Optional[FlatTrainState] = None
        self.rounds = 0                         # steps taken this session
        self._step_fn = None
        self._jitted = None
        self._runner = None

    # ------------------------------------------------------- constructors

    @classmethod
    def create(cls, config: TrainerConfig,
               params: Optional[Pytree] = None) -> "Trainer":
        """Fresh session: init params from ``config.seed`` (or adopt the
        given pytree) and build the flat train state on the engine's
        shardings."""
        t = cls(config)
        if params is None:
            params = lm_init(jax.random.PRNGKey(config.seed), t.cfg)
        t.state = init_flat_train_state(t.engine, t.opt, params,
                                        algo=t.server_rule)
        return t

    @classmethod
    def restore(cls, ckpt_dir: str, config: TrainerConfig,
                step: Optional[int] = None) -> "Trainer":
        """Resume a session from ``ckpt_dir`` — flat or legacy-pytree format,
        auto-dispatched; ``step`` None loads the latest.  The session's
        round counter resumes from the checkpoint step, so periodic saves
        continue the step sequence instead of rewinding it."""
        from ..checkpoint import latest_step
        t = cls(config)
        # restore into a zero-valued state (cheap: no lm_init of params that
        # the checkpoint immediately overwrites; slots/server slabs are
        # zero-init anyway, which is exactly what a legacy params-only
        # checkpoint should leave in place)
        t.state = t._shard(restore_train_state(ckpt_dir, step,
                                               t._zero_state(),
                                               t.engine.spec))
        t.rounds = step if step is not None else (latest_step(ckpt_dir) or 0)
        return t

    def _shard(self, state: FlatTrainState) -> FlatTrainState:
        """Land ``state`` on the session's P-axis shardings (checkpoint
        restores rebuild leaves host-side, dropping any mesh placement)."""
        if self.engine.mesh is None:
            return state
        from ..sharding import flat_train_state_shardings
        sh = flat_train_state_shardings(self.engine.spec, self.engine.mesh,
                                        self.engine.paxes, state.opt,
                                        server_like=state.engine)
        return jax.device_put(state, sh)

    @property
    def server_rule(self):
        """The rule shaping ``state.engine``: the round rule when the algo
        has one, else the arrival rule (both granularities of one name
        share the server state — e.g. dude's ``EngineState``)."""
        return self.algo if self.algo is not None else self.async_algo

    def _zero_state(self) -> FlatTrainState:
        """A zero-valued ``FlatTrainState`` on the session's shardings."""
        pf = jnp.zeros((self.engine.P,), jnp.float32)
        return self._shard(FlatTrainState(pf, self.fopt.init(pf),
                                          self.server_rule.init()))

    @classmethod
    def abstract(cls, config: TrainerConfig) -> "Trainer":
        """Shapes-only session (state stays None): for lowering, dry-runs
        and HLO analysis via ``input_specs`` / ``step_fn``."""
        return cls(config)

    # -------------------------------------------------------------- step

    @property
    def step_fn(self):
        """The unjitted canonical step, built once per session:
        ``(state, batch, start_mask, commit_mask) -> (state, metrics)``.
        A stable function object, so repeated ``jax.jit(trainer.step_fn)``
        calls hit one jit cache entry."""
        if self.algo is None:
            raise ConfigError(
                f"algo {self.config.algo!r} is arrival-granularity only; "
                "drive it with trainer.run_async (round options: "
                f"{ROUND_ALGOS})")
        if self._step_fn is None:
            self._step_fn = make_train_step(
                self.cfg, self.mesh, self.opt, self.dude_cfg,
                options=self.options, engine=self.engine, algo=self.algo)
        return self._step_fn

    def _jit(self):
        if self._jitted is None:
            self._jitted = jax.jit(self.step_fn, donate_argnums=(0,))
        return self._jitted

    def step(self, batch: Pytree, start_mask, commit_mask) -> dict:
        """Advance one semi-async round; updates ``self.state`` in place and
        returns the metrics dict (``loss``, ``applied``)."""
        if self.state is None:
            raise ConfigError(
                "abstract session has no state; use Trainer.create/restore")
        with spans.span(spans.STEP, round=self.rounds):
            self.state, metrics = self._jit()(
                self.state, batch, jnp.asarray(start_mask),
                jnp.asarray(commit_mask))
        self.rounds += 1
        return metrics

    # ------------------------------------------------------------- async

    def run_async(self, arrivals, total_iters: int, sample_fn,
                  *, record_every: int = 10, eval_fn=None, ema: float = 0.9,
                  max_time: Optional[float] = None,
                  seed: Optional[int] = None, key_mode: str = "arrival",
                  record_digests: bool = False):
        """Drive ``total_iters`` per-arrival server iterations through the
        event-driven ``runtime.AsyncRunner`` — one ``engine.commit`` (or
        ASGD arrival rule) + flat optimizer apply per gradient arrival, on
        this session's train state.

        ``arrivals`` is a ``runtime.ArrivalProcess`` or a kind name
        (``"fixed"`` / ``"exp"``; ``"trace"`` needs a process built via
        ``runtime.make_arrivals`` or ``TraceArrivals``).  ``sample_fn(
        worker, rng) -> batch`` draws one worker's batch (leaves WITHOUT
        the round step's worker axis).  Updates ``self.state`` and advances
        ``self.rounds`` by the applied iterations; returns the
        ``runtime.AsyncResult`` (records, staleness stats, and the recorded
        ``ArrivalTrace`` for replay).  ``seed`` defaults to ``config.seed +
        self.rounds`` so segmented runs (repeated run_async calls on one
        session) continue the sampling/key stream instead of replaying it;
        pass it explicitly (e.g. the recording run's) for trace-replay
        equivalence.  When ``config.scenario`` is not ``"none"`` the
        arrival process is wrapped in the named client-state scenario
        (``runtime.make_scenario``: dropout/reconnect, partial gradients,
        availability cycles) — except trace replays and processes that are
        already a ``ClientStateProcess``, which carry their own client
        state.  See docs/async.md.
        """
        from ..runtime import make_arrivals, make_scenario
        from ..runtime.arrivals import ClientStateProcess, TraceArrivals
        from ..runtime.runner import AsyncRunner
        if self.async_algo is None:
            raise ConfigError(
                f"algo {self.config.algo!r} has no arrival-granularity "
                f"rule; async options: {ASYNC_ALGOS}")
        if self.state is None:
            raise ConfigError(
                "abstract session has no state; use Trainer.create/restore")
        if seed is None:
            seed = self.config.seed + self.rounds
        if isinstance(arrivals, str):
            # convenience fleet (unit/homogeneous durations), seeded per
            # segment so repeated runs draw fresh schedules; for the
            # speed-model-based heterogeneous fleet build the process
            # explicitly (as launch/train.py does)
            arrivals = make_arrivals(arrivals, self.cfg.n_workers, seed=seed)
        if self.config.scenario != "none" and not isinstance(
                arrivals, (TraceArrivals, ClientStateProcess)):
            arrivals = make_scenario(self.config.scenario, arrivals,
                                     seed=seed)
        if self._runner is None:
            self._runner = AsyncRunner(
                self.engine, self.async_algo, self.opt,
                self._model_grad_fn(),
                queue_depth=self.config.arrival_queue_depth,
                max_in_flight=self.config.max_in_flight)
        res = self._runner.run(
            arrivals, total_iters, sample_fn, self.state,
            seed=seed, record_every=record_every,
            eval_fn=eval_fn, ema=ema, max_time=max_time,
            key_mode=key_mode, record_digests=record_digests)
        self.state = res.state
        self.rounds += int(res.stats.iters)
        return res

    def serve_async(self, links, total_iters: int, *,
                    record_every: int = 10, eval_fn=None, ema: float = 0.9,
                    seed: Optional[int] = None, accept_fn=None,
                    max_wall_s: Optional[float] = None):
        """Multi-host twin of ``run_async``: drive ``total_iters`` server
        iterations from commit frames arriving on ``links`` (connected
        ``runtime.transport`` endpoints, e.g. ``runtime.accept_links``
        output) instead of a simulated arrival process.

        The transport knobs come from ``config.transport``
        (``TransportPolicy``); ``accept_fn`` (e.g.
        ``runtime.poll_accept_fn(listener)``) enables mid-run worker
        reconnects.  Mid-run server-side checkpointing follows the
        config's ``CheckpointPolicy`` — unlike the single-process runner,
        the hosted loop CAN save every ``every`` applied iterations because
        it owns the arrival loop.  Updates ``self.state``/``self.rounds``
        and returns the ``runtime.AsyncResult`` whose recorded trace
        replays bit-for-bit through ``run_async(TraceArrivals(trace), ...,
        key_mode="worker")``.  See docs/async.md ("Multi-host transport").
        """
        from ..runtime.hostloop import HostRunner
        from ..runtime.runner import AsyncRunner
        if self.async_algo is None:
            raise ConfigError(
                f"algo {self.config.algo!r} has no arrival-granularity "
                f"rule; async options: {ASYNC_ALGOS}")
        if self.state is None:
            raise ConfigError(
                "abstract session has no state; use Trainer.create/restore")
        if seed is None:
            seed = self.config.seed + self.rounds
        if self._runner is None:
            self._runner = AsyncRunner(
                self.engine, self.async_algo, self.opt,
                self._model_grad_fn(),
                queue_depth=self.config.arrival_queue_depth,
                max_in_flight=self.config.max_in_flight)
        tp = self.config.transport
        host = HostRunner(self._runner, heartbeat_s=tp.heartbeat_s,
                          dead_after_s=tp.dead_after_s, poll_s=tp.poll_s,
                          hello_timeout_s=tp.hello_timeout_s,
                          allow_reconnect=tp.allow_reconnect)
        pol = self.config.checkpoint
        ckpt_fn = None
        if pol.directory and pol.every:
            def ckpt_fn(state, it):
                save_checkpoint(pol.directory, self.rounds + it, state,
                                flat_spec=self.engine.spec)
        res = host.serve(links, total_iters, self.state, seed=seed,
                         record_every=record_every, eval_fn=eval_fn,
                         ema=ema, accept_fn=accept_fn,
                         checkpoint_every=pol.every or None,
                         checkpoint_fn=ckpt_fn, max_wall_s=max_wall_s)
        self.state = res.state
        self.rounds += int(res.stats.iters)
        return res

    def _model_grad_fn(self):
        """One worker's stochastic gradient of the session's model:
        ``(params_pytree, batch, key) -> (loss, grads_pytree)`` (the
        ``simulate``/``AsyncRunner`` contract; ``key`` rides for parity
        with data pipelines that consume it)."""
        from ..models import loss_fn
        from ..sharding import make_shard_hook
        cfg, shard = self.cfg, make_shard_hook(self.mesh)

        def grad_fn(params, batch, key):
            (_, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg, shard=shard), has_aux=True
            )(params)
            return metrics["loss"], grads

        return grad_fn

    # ------------------------------------------------------------- views

    def params(self) -> Pytree:
        """The master params, unraveled to the model's pytree layout (per-
        leaf target dtypes from the spec's segment table)."""
        return self.engine.spec.unravel(self.state.params)

    def param_count(self) -> int:
        return self.engine.spec.size

    # ------------------------------------------------------- checkpoints

    def save(self, directory: Optional[str] = None,
             step: Optional[int] = None) -> str:
        """Write a flat-format checkpoint (spec segment table embedded);
        defaults: the config's checkpoint directory, the session round."""
        directory = directory or self.config.checkpoint.directory
        if directory is None:
            raise ConfigError("no checkpoint directory configured or given")
        return save_checkpoint(directory, self.rounds if step is None
                               else step, self.state,
                               flat_spec=self.engine.spec)

    def maybe_save(self) -> Optional[str]:
        """Periodic save per the config's ``CheckpointPolicy`` (no-op unless
        ``every`` divides the current round)."""
        pol = self.config.checkpoint
        if pol.directory and pol.every and self.rounds % pol.every == 0:
            return self.save()
        return None

    # ------------------------------------------------- lowering plumbing

    def state_specs(self):
        """(ShapeDtypeStructs, shardings) of the ``FlatTrainState``."""
        return abstract_train_state(self.cfg, self.mesh, self.opt,
                                    self.dude_cfg, options=self.options,
                                    engine=self.engine, algo=self.server_rule)

    def input_specs(self, shape_name: str = "train_4k"):
        """Shapes and shardings of the FULL step signature
        ``(state, batch, start_mask, commit_mask)`` — feeds
        ``launch/dryrun.py`` / ``launch/hlo_analysis.py`` unchanged."""
        st_shapes, st_sh = self.state_specs()
        (b_shapes, mask_sds), (b_sh, mask_sh) = train_batch_specs(
            self.cfg, self.mesh, shape_name)
        return ((st_shapes, b_shapes, mask_sds, mask_sds),
                (st_sh, b_sh, mask_sh, mask_sh))

    def lower(self, shape_name: str = "train_4k", donate: bool = True):
        """Lower the jitted step at the named input shape with the session's
        shardings (the dry-run's compile-and-fit proof)."""
        shapes, shardings = self.input_specs(shape_name)
        jitted = jax.jit(
            self.step_fn,
            in_shardings=shardings,
            out_shardings=(shardings[0], None),
            donate_argnums=(0,) if donate else (),
        )
        return jitted.lower(*shapes)
