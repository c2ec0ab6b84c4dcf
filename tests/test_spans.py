"""The program's profiler names (``runtime/spans.py``).

* Device scopes: in the debug info of the lowered round step, every
  ``dot_general`` sits under one layer scope, ``dude.backward``, those of
  the attention core under ``dude.attention`` nested in it, and the fused
  round kernel's call under ``dude.round`` (lowered for the TPU, nothing
  compiled); on the TPU path the attention kernels' calls sit under
  ``dude.attention`` in ``dude.backward``; the arrival path's jits put their
  ops under the scope of their layer.  A private function's ops take the scopes of its
  call sites, as XLA's ``op_name`` does when it inlines the call.
* Host spans: a small ``run_async`` under ``jax.profiler.trace`` has one
  ``dude.arrival`` span per arrival carrying its ids, the children of each
  arrival inside it, one ``dude.deliver`` per arrival, and as many
  ``dude.queue_wait`` spans as ``AsyncResult.queue_waits``; each
  ``Trainer.step`` is one ``dude.step`` span with its round.
"""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import Trainer, TrainerConfig
from repro.launch.steps import make_train_step
from repro.models.config import ModelConfig
from repro.runtime import spans

N = 2
S = 16
# a scope in an op's location: after a '/', or first in a private
# function's (relative) name stack
SCOPE = re.compile(r"(?<=[/\"])(dude\.[a-z_]+)(?=/)")
# ops that compute nothing: their place in a scope does not matter
STRUCTURAL = {"func.return", "func.call", "stablehlo.return",
              "stablehlo.constant", "stablehlo.optimization_barrier",
              "stablehlo.tuple", "stablehlo.get_tuple_element"}


def _cfg(n=N):
    """Two scanned, rematerialised layers, as the chip benchmark runs them
    (private functions in the lowered step)."""
    return ModelConfig(
        name="spans-test-lm", arch_type="dense", num_layers=2, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=32,
        dtype=jnp.float32, remat=True, attn_chunk=16, n_workers=n,
        scan_layers=True)


def _walk(op):
    for region in op.regions:
        for block in region.blocks:
            for o in block.operations:
                yield o
                yield from _walk(o)


def scoped_ops(lowered) -> list:
    """``(op name, scopes, op)`` of every op of a lowered module; the ops of
    a private function add the scopes of the calls that reach it."""
    ir = lowered.compiler_ir("stablehlo")
    funcs = {f.attributes["sym_name"].value: f for f in ir.body.operations}
    reach = {"main": frozenset()}
    todo = ["main"]
    while todo:                 # scopes reaching each function
        fname = todo.pop()
        for o in _walk(funcs[fname]):
            if o.operation.name == "func.call":
                callee = o.attributes["callee"].value
                sc = reach[fname] | frozenset(SCOPE.findall(str(o.location)))
                if callee not in reach or not sc <= reach[callee]:
                    reach[callee] = reach.get(callee, frozenset()) | sc
                    todo.append(callee)
    return [(o.operation.name,
             reach[fname] | frozenset(SCOPE.findall(str(o.location))), o)
            for fname in reach for o in _walk(funcs[fname])]


def _computing(ops):
    return [(name, sc) for name, sc, _ in ops if name not in STRUCTURAL]


@pytest.fixture(scope="module")
def trainer():
    return Trainer.create(TrainerConfig(arch=_cfg(), lr=0.05, seed=1))


def _batch(cfg, key=0):
    toks = jax.random.randint(jax.random.PRNGKey(key),
                              (cfg.n_workers, 1, S), 0, cfg.vocab_size)
    return {"tokens": toks, "labels": toks}


def _lowered_round_step(trainer, seq_len=S):
    """The round step lowered for the TPU (the Mosaic kernels, not their
    CPU interpreter)."""
    eng = dataclasses.replace(trainer.engine, backend="pallas",
                              interpret=False)
    step = make_train_step(trainer.cfg, None, trainer.opt, trainer.dude_cfg,
                           options=trainer.options, engine=eng,
                           algo=trainer.algo)
    m = jnp.ones((N,), bool)
    toks = jnp.zeros((N, 1, seq_len), jnp.int32)
    return jax.jit(step).trace(trainer.state, {"tokens": toks,
                                               "labels": toks}, m,
                               m).lower(lowering_platforms=("tpu",))


def _kernels(ops) -> list:
    return [sc for name, sc, o in ops if name == "stablehlo.custom_call"
            and "tpu_custom_call" in str(o.attributes["call_target_name"])]


def _layer_scopes(sc) -> frozenset:
    return sc - {spans.ATTENTION}


def test_round_step_scopes(trainer):
    """Every matmul is the backward's, those of the attention core under
    ``dude.attention`` too, the kernel is the round's, and no op carries two
    layer scopes."""
    ops = scoped_ops(_lowered_round_step(trainer))
    dots = [sc for name, sc, _ in ops if name == "stablehlo.dot_general"]
    assert dots and all(_layer_scopes(sc) == {spans.BACKWARD} for sc in dots)
    assert {spans.BACKWARD, spans.ATTENTION} in dots
    assert _kernels(ops) == [frozenset({spans.ROUND})]
    assert all(len(_layer_scopes(sc)) <= 1 for _, sc, _ in ops)
    seen = set().union(*(sc for _, sc, _ in ops))
    assert seen == {spans.UNRAVEL, spans.BACKWARD, spans.RAVEL, spans.ROUND,
                    spans.ATTENTION}


def test_round_step_attention_kernel_scopes(trainer, monkeypatch):
    """On the TPU path (the backend steered here; S a multiple of 128) the
    attention kernels' calls, forward and backward, sit under
    ``dude.attention`` in ``dude.backward``, and the round kernel alone
    under ``dude.round``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops = scoped_ops(_lowered_round_step(trainer, seq_len=128))
    kernels = _kernels(ops)
    attn = [sc for sc in kernels if sc != {spans.ROUND}]
    assert kernels.count(frozenset({spans.ROUND})) == 1
    # forward, the remat's forward, dkv and dq, in each of two layers (one
    # scanned body)
    assert len(attn) >= 3
    assert all(sc == {spans.BACKWARD, spans.ATTENTION} for sc in attn)


@pytest.mark.parametrize("commit_format", ["f32", "int8_ef"])
def test_arrival_jit_scopes(commit_format):
    """Each jit of the arrival path is a named function whose computing ops
    sit under its layer's one scope; the arrival step splits into the
    commit and the apply."""
    cfg = _cfg(4)
    t = Trainer.create(TrainerConfig(arch=cfg, server_backend="reference",
                                     commit_format=commit_format, lr=0.05))
    from repro.runtime.runner import AsyncRunner
    r = AsyncRunner(t.engine, t.async_algo, t.opt, t._model_grad_fn())
    params = t.engine.spec.unravel(t.state.params)
    batch = {k: v[0] for k, v in _batch(cfg).items()}
    key = jax.random.PRNGKey(0)
    st = t.state
    w = jnp.int32(1)
    jits = {spans.BACKWARD: (r._grad, (params, batch, key)),
            spans.RAVEL: (r._ravel, (params,)),
            spans.UNRAVEL: (r._unravel, (st.params,))}
    if commit_format != "f32":
        q, s = r._snap_encode(st.params, st.params)
        jits[spans.UNRAVEL] = (r._snap_unravel, (st.params, q, s))
    for scope, (fn, args) in jits.items():
        ops = _computing(scoped_ops(fn.lower(*args)))
        if scope == spans.BACKWARD:
            # the remat'd forward's constant tables (RoPE, masks) are
            # hoisted out of every scope; the matmuls are not
            dots = [sc for n, sc in ops if n == "stablehlo.dot_general"]
            assert dots and all(_layer_scopes(sc) == {scope} for sc in dots)
            assert all(len(_layer_scopes(sc)) <= 1 for _, sc in ops)
        else:
            assert ops and all(sc == {scope} for _, sc in ops), scope
    gflat = r._ravel(params)
    ops = _computing(scoped_ops(r._step.lower(
        st.params, st.opt, st.engine, w, gflat, jnp.int32(1))))
    assert all(sc in ({spans.COMMIT}, {spans.APPLY}) for _, sc in ops)
    assert {spans.COMMIT, spans.APPLY} == set().union(*(sc for _, sc in ops))
    names = {fn.__name__ for fn, _ in jits.values()}
    assert "<lambda>" not in names


# ---------------------------------------------------------- host spans


def _host_spans(log_dir) -> list:
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("dude."):
                        out.append((e.name, e.start_ns, e.end_ns,
                                    {k: int(v) for k, v in list(e.stats)}))
    return sorted(out, key=lambda r: (r[1], -r[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory, trainer):
    """One profiled session: two round steps, then 9 arrivals."""
    cfg = trainer.cfg
    key = jax.random.PRNGKey(2)

    def sample_fn(i, rng):
        toks = jax.random.randint(jax.random.fold_in(key, i), (1, S), 0,
                                  cfg.vocab_size)
        return {"tokens": toks, "labels": toks}

    ones = jnp.ones((N,), bool)
    trainer.step(_batch(cfg, 1), ones, ones)       # compile outside
    trainer.run_async("fixed", 3, sample_fn, record_every=2)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    r0 = trainer.rounds
    with jax.profiler.trace(log_dir):
        for k in range(2):
            jax.block_until_ready(trainer.step(_batch(cfg, k), ones, ones))
        res = trainer.run_async("fixed", 9, sample_fn, record_every=4)
        jax.block_until_ready(res.state)
    return _host_spans(log_dir), res, r0


def test_one_step_span_per_round(traced):
    rows, _, r0 = traced
    steps = [r for r in rows if r[0] == spans.STEP]
    assert [r[3] for r in steps] == [{"round": r0}, {"round": r0 + 1}]


def test_one_arrival_span_per_arrival_with_ids(traced):
    rows, res, _ = traced
    arr = [r for r in rows if r[0] == spans.ARRIVAL]
    assert len(arr) == res.stats.arrivals == 9
    assert [r[3]["arrival"] for r in arr] == list(range(9))
    assert [r[3]["worker"] for r in arr] == res.trace.worker.tolist()
    assert all(r[3]["tau"] >= 1 for r in arr)
    deliver = [r for r in rows if r[0] == spans.DELIVER]
    assert [r[3]["arrival"] for r in deliver] == list(range(9))


def test_children_lie_inside_their_arrival(traced):
    rows, res, _ = traced
    arr = [r for r in rows if r[0] == spans.ARRIVAL]
    kids = {spans.SAMPLE, spans.GRAD, spans.COMMIT, spans.QUEUE_WAIT,
            spans.RECORD}
    count = dict.fromkeys(kids, 0)
    for name, s, e, _ in rows:
        if name in kids:
            count[name] += 1
            assert any(a[1] <= s and e <= a[2] for a in arr), name
    assert count[spans.SAMPLE] == count[spans.GRAD] == 9
    assert count[spans.COMMIT] == 9
    assert count[spans.RECORD] == len(res.losses) == 2
    for d in (r for r in rows if r[0] == spans.DELIVER):
        assert not any(a[1] <= d[1] < a[2] for a in arr)


def test_queue_wait_spans_count_queue_waits(traced):
    rows, res, _ = traced
    waits = sum(1 for r in rows if r[0] == spans.QUEUE_WAIT)
    assert res.queue_waits > 0
    assert waits == res.queue_waits


def test_spans_cost_little_with_profiler_off():
    """A span is a no-op to the math and cheap to open with no profiler."""
    import time
    t0 = time.perf_counter()
    for i in range(10000):
        with spans.span(spans.ARRIVAL, arrival=i, worker=1, tau=2):
            pass
    assert (time.perf_counter() - t0) / 10000 < 100e-6
