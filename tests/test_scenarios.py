"""Client-state scenario acceptance tests (docs/async.md, "Client-state
scenarios").

* Chaos replay: every scenario kind (dropout, reconnect, partial
  gradients, sin/lognormal/skew availability, full chaos) drives BOTH the
  event-driven simulator and the production AsyncRunner to BIT-IDENTICAL
  parameters — fresh identical processes agree, and a recorded v3
  ``ArrivalTrace`` replays through either harness (params + digests).
* Loop invariants under every scenario: arrivals stay time-ordered, the
  ``max_in_flight`` bound is respected (and ``max_in_flight=1`` forces
  ``tau == 1``), events align one-per-arrival, permanent dropout
  terminates the run instead of hanging it.
* Trace schema v3: events survive a save/load roundtrip exactly, v2
  files upgrade in place (``events is None``), unknown schemas and
  mismatched event counts are rejected.
* Staleness-adaptive rules: s(τ) ∈ (0, 1], monotone non-increasing,
  all rules agree at τ = 0 (hypothesis-property-swept when hypothesis is
  installed, deterministically otherwise); the flat-slab ``dude_hinge``
  arrival matches a numpy reference bitwise; ``dude_const`` IS ``dude``;
  the sharded staleness arrival step compiles to ZERO collectives.
* ``make_scenario`` / ``make_arrivals`` / ``TrainerConfig`` reject
  unknown kinds, unknown options and invalid values with the typed
  ``ConfigError``.
* Convergence regression (``-m slow``, nightly CI): under a
  label-skew-correlated availability scenario DuDe's final loss beats
  vanilla ASGD by a seeded margin on the class-Gaussian CNN problem.

Multi-device tests follow the test_runtime.py pattern: skipped below 8
devices and re-run by ``test_scenarios_sharded_suite_subprocess`` under
``--xla_force_host_platform_device_count=8``; CI also runs this file
in-process on the 8-device host mesh.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import NDEV, collective_counts, multidevice, p_mesh
from repro.api.config import ConfigError
from repro.core import make_algo, simulate, truncated_normal_speeds
from repro.core.algos import (HINGE_A, HINGE_B, POLY_A, STALENESS_ASYNC,
                              STALENESS_RULES, make_async_algo,
                              staleness_weight)
from repro.core.engine import DuDeEngine
from repro.core.flatten import make_flat_spec
from repro.optim import sgd
from repro.runtime import (
    ArrivalTrace, ClientEvent, ClientStateProcess, FixedArrivals,
    LognormalAvailability, SinAvailability, SkewAvailability, TraceArrivals,
    make_arrivals, make_scenario,
)
from repro.runtime.arrivals import TRACE_SCHEMA, SCENARIO_KINDS, Arrival
from repro.runtime.runner import AsyncRunner

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis
    HAVE_HYPOTHESIS = False

N = 5
LR = 0.05
SEED = 3
TOTAL = 30


def _tree():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.normal(size=(3, 4)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=5), jnp.float32)}


_TARGETS = jnp.asarray(np.random.default_rng(42).normal(size=(N, 3, 4)),
                       jnp.float32)


def _sample_fn(i, rng):
    return {"i": jnp.int32(i),
            "noise": jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)}


def _loss(p, batch):
    t = _TARGETS[batch["i"]] + 0.1 * batch["noise"]
    return 0.5 * jnp.sum((p["w"] - t) ** 2) + 0.5 * jnp.sum(p["b"] ** 2)


def _grad_fn(params, batch, key):
    loss, g = jax.value_and_grad(_loss)(params, batch)
    return loss, g


def _sim(name, process, total=TOTAL):
    speeds = truncated_normal_speeds(N, std=1.0, seed=1)
    return simulate(make_algo(name, N), speeds, _grad_fn, _sample_fn,
                    _tree(), lr=LR, total_iters=total, seed=SEED,
                    record_every=10, arrivals=process)


def _runner(algo, process, total=TOTAL, mesh=None, max_in_flight=None,
            record_digests=False):
    tree = _tree()
    spec = make_flat_spec(tree, mesh_axis_size=NDEV if mesh else 1)
    eng = DuDeEngine(spec=spec, n_workers=N, interpret=True, mesh=mesh,
                     axis_name="p" if mesh else None)
    runner = AsyncRunner(eng, algo, sgd(LR), _grad_fn,
                         max_in_flight=max_in_flight)
    state = runner.init_state(tree)
    out = runner.run(process, total, _sample_fn, state, seed=SEED,
                     record_every=10, record_digests=record_digests)
    return eng, out


# Every scenario kind as explicit ClientStateProcess kwargs (so tests can
# construct the identical process repeatedly).  "reconnect" stresses the
# dropout/reconnect cycle harder than the factory default.
SCENARIOS = {
    "dropout": dict(dropout_rate=0.25, reconnect_mean=1.5),
    "reconnect": dict(dropout_rate=0.5, reconnect_mean=0.5),
    "partial": dict(partial_min=0.3),
    "sin": dict(availability=SinAvailability(period=6.0, slot=0.25)),
    "lognormal": dict(availability=LognormalAvailability(sigma=1.2, seed=7)),
    "skew": dict(availability=SkewAvailability(np.linspace(0.0, 1.0, N))),
    "chaos": dict(dropout_rate=0.15, reconnect_mean=1.0, partial_min=0.5,
                  responsiveness_sigma=0.4,
                  availability=SinAvailability(period=6.0)),
}


def _proc(kind):
    return ClientStateProcess(FixedArrivals(np.linspace(0.7, 1.9, N)),
                              seed=11, **SCENARIOS[kind])


# ------------------------------------------------- simulator <-> runner


@pytest.mark.parametrize("kind", sorted(SCENARIOS))
def test_scenario_sim_runner_bitwise(kind):
    """THE chaos acceptance criterion: under every client-state scenario a
    fresh-process runner run, a fresh-process simulator run, and a runner
    replay of the simulator's recorded v3 trace all produce BIT-IDENTICAL
    parameters (scenario outcomes depend only on (seed, worker, job), and
    completeness scaling commutes with ravel)."""
    res = _sim("dude_asgd", _proc(kind))
    assert res.trace.events is not None
    assert len(res.trace.events) == len(res.trace)

    for process in (_proc(kind), TraceArrivals(res.trace)):
        eng, out = _runner("dude", process)
        back = eng.spec.unravel(out.state.params)
        for k, leaf in res.params.items():
            np.testing.assert_array_equal(
                np.asarray(back[k]), np.asarray(leaf),
                err_msg=f"{kind}/{type(process).__name__}/{k}")
        assert out.tau_max == res.tau_max
        assert out.n_grads == res.n_grads
        np.testing.assert_array_equal(out.trace.worker, res.trace.worker)
        np.testing.assert_allclose(out.trace.t_arrive, res.trace.t_arrive)
        got = [e.to_row() for e in out.trace.events]
        want = [e.to_row() for e in res.trace.events]
        assert got == want


def test_scenario_routed_replay_bitwise():
    """A routed discipline under chaos still replays bitwise (the routing
    rng draw order is part of the recorded semantics)."""
    res = _sim("uniform_asgd", _proc("chaos"))
    eng, out = _runner("uniform_asgd", TraceArrivals(res.trace))
    back = eng.spec.unravel(out.state.params)
    for k, leaf in res.params.items():
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(leaf))


def test_runner_self_replay_digests_staleness_chaos():
    """dude_hinge under full chaos: the runner replaying its own recorded
    trace reproduces params, per-arrival commit digests, losses and times
    bitwise — staleness damping and partial-gradient scaling included."""
    eng, out = _runner("dude_hinge", _proc("chaos"), record_digests=True)
    assert out.digests is not None and len(out.digests) == out.n_grads
    eng2, rep = _runner("dude_hinge", TraceArrivals(out.trace),
                        record_digests=True)
    np.testing.assert_array_equal(np.asarray(rep.state.params),
                                  np.asarray(out.state.params))
    assert rep.digests == out.digests
    np.testing.assert_array_equal(rep.losses, out.losses)
    np.testing.assert_array_equal(rep.times, out.times)


@multidevice
@pytest.mark.parametrize("algo", ["dude", "dude_hinge"])
def test_scenario_sharded_replay_bitwise(algo):
    """Chaos runs replay bit-for-bit with the engine P-axis sharded on the
    8-device mesh: commit and the staleness mix are elementwise on P (the
    worker-row gather slices the replicated n axis shard-locally)."""
    eng, out = _runner(algo, _proc("chaos"))
    eng_s, out_s = _runner(algo, TraceArrivals(out.trace), mesh=p_mesh())
    back = eng.spec.unravel(out.state.params)
    back_s = eng_s.spec.unravel(out_s.state.params)
    for k in back:
        np.testing.assert_array_equal(np.asarray(back_s[k]),
                                      np.asarray(back[k]),
                                      err_msg=f"{algo}/{k}")
    assert out_s.tau_max == out.tau_max


@multidevice
def test_staleness_arrival_step_zero_collective_hlo_sharded():
    """The staleness-damped arrival step on the sharded engine compiles to
    ZERO collectives: s(τ) is scalar math and the g_workers[w] gather is
    along the replicated worker axis, so the mix never crosses shards."""
    mesh = p_mesh()
    tree = _tree()
    spec = make_flat_spec(tree, mesh_axis_size=NDEV)
    eng = DuDeEngine(spec=spec, n_workers=N, interpret=True, mesh=mesh,
                     axis_name="p")
    runner = AsyncRunner(eng, "dude_hinge", sgd(LR), _grad_fn)
    state = runner.init_state(tree)
    gflat = runner._ravel(jax.tree.map(jnp.ones_like, tree))
    hlo = runner._step.lower(state.params, state.opt, state.engine,
                             jnp.int32(1), gflat,
                             jnp.int32(6)).compile().as_text()
    counts = {k: v for k, v in collective_counts(hlo).items() if v}
    assert not counts, f"staleness arrival step has collectives: {counts}"


# ----------------------------------------------------- loop invariants


@pytest.mark.parametrize("kind", sorted(SCENARIOS))
def test_scenario_loop_invariants(kind):
    """Arrivals stay time-ordered with positive durations, events align
    one-per-arrival, and the in-flight bound holds under every scenario."""
    eng, out = _runner("dude", _proc(kind), max_in_flight=3)
    tr = out.trace
    assert out.stats.iters == TOTAL
    assert np.all(np.diff(tr.t_arrive) >= 0)
    assert np.all(tr.t_arrive > tr.t_dispatch)
    assert len(tr.events) == len(tr)
    assert out.stats.max_in_flight <= 3
    for e in tr.events:
        assert 0.0 < e.completeness <= 1.0
        assert e.drops >= 0 and e.wait >= 0.0 and e.outage >= 0.0
    stats = tr.event_stats()
    assert stats["events"] == len(tr)
    if kind in ("dropout", "reconnect", "chaos"):
        assert stats["dropouts"] > 0 and stats["outage_time"] > 0.0
    if kind in ("partial", "chaos"):
        assert stats["partial_jobs"] > 0
        assert stats["mean_completeness"] < 1.0
        lo = SCENARIOS[kind].get("partial_min", SCENARIOS["partial"]["partial_min"])
        assert all(e.completeness >= lo for e in tr.events)
    if kind in ("sin", "lognormal", "skew", "chaos"):
        assert stats["wait_time"] > 0.0


def test_serial_in_flight_staleness_ceiling():
    """max_in_flight=1 serializes the fleet, so staleness is bounded by the
    warmup: a worker's FIRST job still carries the initial version-0 model
    (at most N iterations old by the time it runs); every later job computes
    on the freshest model (tau = 1).  The ceiling is therefore N, and an
    unbounded run can exceed it."""
    eng, out = _runner("dude", _proc("chaos"), max_in_flight=1)
    assert out.stats.max_in_flight == 1
    assert 1 <= out.tau_max <= N
    assert out.stats.iters == TOTAL


def test_permanent_dropout_terminates_run():
    """reconnect_mean=None kills a dropped worker mid-compute (infinite
    duration); the loop finishes the survivors and stops instead of
    hanging — and the truncated trace still replays bitwise."""
    proc = ClientStateProcess(FixedArrivals(np.ones(N)), seed=2,
                              dropout_rate=0.5, reconnect_mean=None)
    eng, out = _runner("dude", proc, total=200)
    assert out.stats.iters < 200          # the fleet died before the target
    assert out.stats.iters == len(out.trace) > 0
    eng2, rep = _runner("dude", TraceArrivals(out.trace), total=200)
    np.testing.assert_array_equal(np.asarray(rep.state.params),
                                  np.asarray(out.state.params))


# ---------------------------------------------------- trace schema v3


class TestTraceSchemaV3:
    def _chaos_trace(self):
        return _sim("dude_asgd", _proc("chaos")).trace

    def test_v3_roundtrip_preserves_events(self, tmp_path):
        tr = self._chaos_trace()
        path = tr.save(str(tmp_path / "t.json"))
        with open(path) as f:
            d = json.load(f)
        assert d["schema"] == TRACE_SCHEMA == 3
        assert len(d["events"]) == len(tr)
        back = ArrivalTrace.load(path)
        assert [e.to_row() for e in back.events] == \
               [e.to_row() for e in tr.events]
        # completeness survives JSON exactly (it is an exact float32)
        for e in back.events:
            assert e.completeness == float(np.float32(e.completeness))
        assert back.event_stats() == tr.event_stats()

    def test_v2_file_upgrades_without_events(self, tmp_path):
        path = tmp_path / "v2.json"
        path.write_text(json.dumps({
            "schema": 2, "n": 2, "worker": [0, 1],
            "t_dispatch": [0.0, 0.0], "t_arrive": [1.0, 2.0],
            "digest": ["aa" * 4, "bb" * 4]}))
        tr = ArrivalTrace.load(str(path))
        assert tr.events is None
        assert tr.event_stats() == {}
        assert tr.digest == ("aa" * 4, "bb" * 4)

    def test_future_schema_rejected(self, tmp_path):
        path = tmp_path / "v9.json"
        path.write_text(json.dumps({
            "schema": TRACE_SCHEMA + 1, "n": 1, "worker": [0],
            "t_dispatch": [0.0], "t_arrive": [1.0]}))
        with pytest.raises(ValueError, match="schema"):
            ArrivalTrace.load(str(path))

    def test_event_count_mismatch_rejected(self):
        with pytest.raises(ValueError, match="events"):
            ArrivalTrace.from_arrivals(
                2, [Arrival(0, 0, 0.0, 1.0)],
                events=[ClientEvent(), ClientEvent()])


# ------------------------------------------------------ factory errors


class TestFactoryValidation:
    def test_unknown_scenario_kind(self):
        with pytest.raises(ConfigError, match="unknown scenario kind"):
            make_scenario("blackout", FixedArrivals(np.ones(N)))

    def test_unknown_scenario_option(self):
        with pytest.raises(ConfigError, match="unknown option"):
            make_scenario("dropout", FixedArrivals(np.ones(N)),
                          dropout_prob=0.5)

    def test_invalid_scenario_value(self):
        with pytest.raises(ConfigError, match="dropout_rate"):
            make_scenario("dropout", FixedArrivals(np.ones(N)),
                          dropout_rate=1.5)
        with pytest.raises(ConfigError, match="partial_min"):
            make_scenario("partial", FixedArrivals(np.ones(N)),
                          partial_min=0.0)

    def test_none_is_identity(self):
        base = FixedArrivals(np.ones(N))
        assert make_scenario("none", base) is base
        with pytest.raises(ConfigError, match="unknown option"):
            make_scenario("none", base, dropout_rate=0.1)

    def test_every_kind_builds(self):
        base = FixedArrivals(np.ones(N))
        for kind in SCENARIO_KINDS:
            proc = make_scenario(kind, base, seed=1)
            assert proc.n == N

    def test_make_arrivals_unknown_kind(self):
        with pytest.raises(ConfigError, match="unknown arrival kind"):
            make_arrivals("poisson", N)

    def test_make_arrivals_invalid_values(self):
        with pytest.raises(ConfigError, match="fixed"):
            make_arrivals("fixed", N, times=[-1.0] * N)
        with pytest.raises(ConfigError, match="trace"):
            make_arrivals("trace", N)

    def test_config_error_is_value_error(self):
        assert issubclass(ConfigError, ValueError)

    def test_trainer_config_scenario_knobs(self):
        from repro.api import TrainerConfig
        from repro.models.config import ModelConfig
        cfg = ModelConfig(
            name="scenario-test-lm", arch_type="dense", num_layers=1,
            d_model=32, num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=32,
            dtype=jnp.float32, remat=False, attn_chunk=16, n_workers=4)
        for kind in SCENARIO_KINDS:
            TrainerConfig(arch=cfg, algo="dude", scenario=kind)
        with pytest.raises(ConfigError, match="unknown scenario"):
            TrainerConfig(arch=cfg, scenario="blackout")
        TrainerConfig(arch=cfg, algo="dude_hinge")
        with pytest.raises(ConfigError, match="f32"):
            TrainerConfig(arch=cfg, algo="dude_hinge",
                          commit_format="int8_ef")


# --------------------------------------------------- staleness weights


def _weight(rule, tau):
    return float(staleness_weight(rule, jnp.int32(tau)))


TAUS = [0, 1, 2, 3, 4, 5, 6, 8, 16, 64, 1000]


class TestStalenessWeights:
    @pytest.mark.parametrize("rule", STALENESS_RULES)
    def test_in_unit_interval_and_monotone(self, rule):
        ws = [_weight(rule, t) for t in TAUS]
        assert all(0.0 < w <= 1.0 for w in ws)
        assert all(a >= b for a, b in zip(ws, ws[1:]))

    @pytest.mark.parametrize("rule", STALENESS_RULES)
    def test_rules_agree_at_tau_zero(self, rule):
        assert _weight(rule, 0) == 1.0

    def test_known_values(self):
        assert HINGE_A == 10.0 and HINGE_B == 4.0 and POLY_A == 0.5
        assert _weight("hinge", 4) == 1.0
        assert _weight("hinge", 5) == pytest.approx(0.1)
        np.testing.assert_allclose(_weight("poly", 3), 0.5)

    def test_unknown_rule_rejected(self):
        with pytest.raises(ValueError, match="staleness rule"):
            staleness_weight("cosine", jnp.int32(1))

    def test_flat_slab_rule_matches_numpy_reference_bitwise(self):
        """Two staleness-damped commits through the engine == the same
        arithmetic in numpy float32 (mix, delta-fold, division by n) —
        no hidden fusion or reassociation in the compiled arrival rule."""
        tree = {"w": jnp.zeros((7,), jnp.float32)}
        spec = make_flat_spec(tree)
        eng = DuDeEngine(spec=spec, n_workers=3, interpret=True)
        algo = make_async_algo("dude_hinge", eng)
        state = algo.init_fn()
        P = eng.P
        rng = np.random.default_rng(9)
        g1 = np.asarray(rng.normal(size=P), np.float32)
        g2 = np.asarray(rng.normal(size=P), np.float32)
        w, n = 1, np.float32(3)

        state, _ = algo.arrival(state, w, jnp.asarray(g1), tau=2)
        state, gbar = algo.arrival(state, w, jnp.asarray(g2), tau=6)

        s1 = np.float32(_weight("hinge", 2))   # = 1.0 (below the knee)
        s2 = np.float32(_weight("hinge", 6))
        eff1 = s1 * g1 + (np.float32(1.0) - s1) * np.zeros(P, np.float32)
        bar1 = (eff1 - np.float32(0.0)) / n
        eff2 = s2 * g2 + (np.float32(1.0) - s2) * eff1
        bar2 = bar1 + (eff2 - eff1) / n
        np.testing.assert_array_equal(np.asarray(state.g_workers[w]), eff2)
        np.testing.assert_array_equal(np.asarray(state.g_bar), bar2)
        np.testing.assert_array_equal(np.asarray(gbar), bar2)

    def test_dude_const_is_dude_bitwise(self):
        """s(τ) = 1 collapses the staleness family onto plain DuDe — a full
        chaos run under each produces identical parameters."""
        eng_a, out_a = _runner("dude", _proc("chaos"))
        eng_b, out_b = _runner("dude_const", _proc("chaos"))
        np.testing.assert_array_equal(np.asarray(out_a.state.params),
                                      np.asarray(out_b.state.params))

    def test_staleness_rejects_compressed_slab(self):
        tree = _tree()
        eng = DuDeEngine.for_tree(tree, n_workers=N, interpret=True,
                                  commit_format="int8_ef")
        with pytest.raises(ValueError, match="f32"):
            make_async_algo("dude_poly", eng)
        assert sorted(STALENESS_ASYNC) == ["dude_const", "dude_hinge",
                                           "dude_poly"]


if HAVE_HYPOTHESIS:
    class TestStalenessHypothesis:
        @settings(max_examples=60, deadline=None)
        @given(rule=st.sampled_from(STALENESS_RULES),
               tau=st.integers(0, 100_000))
        def test_weight_in_unit_interval(self, rule, tau):
            w = _weight(rule, tau)
            assert 0.0 < w <= 1.0

        @settings(max_examples=60, deadline=None)
        @given(rule=st.sampled_from(STALENESS_RULES),
               tau=st.integers(0, 10_000), step=st.integers(1, 100))
        def test_weight_monotone_non_increasing(self, rule, tau, step):
            assert _weight(rule, tau) >= _weight(rule, tau + step)

        @settings(max_examples=20, deadline=None)
        @given(tau=st.integers(0, 1000))
        def test_hinge_matches_numpy_formula(self, tau):
            want = (1.0 if tau <= HINGE_B
                    else min(1.0, float(np.float32(1.0) / np.float32(
                        np.float32(HINGE_A) * np.float32(tau - HINGE_B)))))
            assert _weight("hinge", tau) == pytest.approx(want, rel=1e-6)


# ----------------------------------------------- convergence regression


@pytest.mark.slow
def test_dude_beats_vanilla_under_label_skew_scenario():
    """Convergence regression (nightly): Dirichlet label-skew partition of
    the class-Gaussian images AND skew-correlated availability — the rare
    labels live on the flakiest clients.  The model is an UNDERPARAMETERIZED
    softmax on pooled features, so the balanced optimum is contested between
    workers: vanilla ASGD's stationary point is the arrival-rate-weighted
    optimum (biased toward the always-online shards), while DuDe's
    dual-delayed average weighs every worker equally regardless of how
    rarely it arrives.  Judged on the BALANCED full-dataset loss, DuDe must
    beat vanilla by a seeded margin (calibrated: observed ~0.08 at the
    pinned seeds, asserted at half that)."""
    from repro.data import (class_gaussian_images, dirichlet_partition,
                            label_distribution, make_sample_fn)

    n, total = 8, 2000
    x, y = class_gaussian_images(n=1024, seed=0)
    shards = dirichlet_partition(y, n, alpha=0.1, seed=0)
    sample_fn = make_sample_fn(x, y, shards, batch=32, seed=0)

    def feats(xb):  # [B,32,32,3] -> [B,48]: 8x8 average pool per channel
        xb = xb.reshape(xb.shape[0], 4, 8, 4, 8, 3).mean(axis=(2, 4))
        return xb.reshape(xb.shape[0], -1)

    def loss_fn(p, batch):
        logits = feats(jnp.asarray(batch["x"], jnp.float32)) @ p["w"] + p["b"]
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, batch["y"][:, None],
                                             axis=-1))

    params0 = {"w": jnp.zeros((48, 10), jnp.float32),
               "b": jnp.zeros((10,), jnp.float32)}

    def grad_fn(params, batch, key):
        return jax.value_and_grad(loss_fn)(params, batch)

    # the recorded metric must be BALANCED (loss over the full dataset):
    # the running train EMA only sees the batches of whoever is online,
    # which is exactly the bias this scenario induces
    eval_batch = {"x": x, "y": y}
    eval_fn = jax.jit(lambda p: loss_fn(p, eval_batch))

    # availability anti-correlated with label coverage: the most
    # label-skewed shards (distribution peaked on one class) get the
    # lowest online probability
    dist = label_distribution(y, shards)          # [n, n_classes]
    skew = dist.max(axis=1)                       # peaked shard = skewed data
    skew = (skew - skew.min()) / max(1e-9, float(np.ptp(skew)))
    speeds = truncated_normal_speeds(n, std=1.0, seed=1)

    def run(name):
        proc = ClientStateProcess(
            FixedArrivals(np.asarray(speeds.times)), seed=5,
            availability=SkewAvailability(skew, beta=0.9, slot=2.0))
        return simulate(make_algo(name, n), speeds, grad_fn, sample_fn,
                        params0, lr=0.05, total_iters=total, seed=SEED,
                        record_every=250, eval_fn=eval_fn, arrivals=proc)

    dude = run("dude_asgd")
    vanilla = run("vanilla_asgd")
    assert np.isfinite(dude.losses[-1]) and np.isfinite(vanilla.losses[-1])
    # DuDe leads at EVERY record point, not just the last
    assert np.all(np.asarray(dude.losses) < np.asarray(vanilla.losses))
    assert dude.losses[-1] < vanilla.losses[-1] - 0.04, (
        f"dude {dude.losses[-1]:.4f} vs vanilla {vanilla.losses[-1]:.4f}")


# ------------------------------------------------------ subprocess driver


def test_scenarios_sharded_suite_subprocess():
    """Run the in-process multidevice tests above on 8 host-platform devices
    (they are skipped in a default single-device session)."""
    if jax.device_count() >= NDEV:
        pytest.skip("already multi-device in-process")
    repo = Path(__file__).resolve().parent.parent
    env = {
        **os.environ,
        "PYTHONPATH": "src",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                      + f" --xla_force_host_platform_device_count={NDEV}"
                      ).strip(),
    }
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(Path(__file__).resolve()),
         "-k", "sharded and not subprocess"],
        capture_output=True, text=True, timeout=540, env=env, cwd=repo,
    )
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert "skipped" not in r.stdout.splitlines()[-1], r.stdout[-500:]
