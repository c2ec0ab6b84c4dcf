"""The program's profiler names: a contract with whoever reads a trace.

Device scopes (``jax.named_scope``) tag the HLO ``op_name`` metadata of the
ops they cover; a profile of a TPU run carries it as each op's ``tf_op``.
Only the attention scope and the two MoE scopes nest, and only inside
``dude.backward`` (they tag the model's ops, which a serving path runs
under no layer scope); every other scoped op belongs to exactly one scope,
and the first ``dude.*`` name in an op name is that of its layer:

* ``dude.unravel``  — flat ``[P]`` params (or a snapshot) -> model pytree;
* ``dude.backward`` — one worker's (or the vmapped workers') forward and
  backward;
* ``dude.ravel``    — gradient pytree(s) -> flat ``[P]`` / ``[n, P]`` slab,
  with its sharding constraints and the data-axis reduce-scatter;
* ``dude.round``    — the round rule and its fused (or gated) apply;
* ``dude.commit`` / ``dude.apply`` — one arrival's server rule and its flat
  optimizer apply;
* inside ``dude.backward``, in every attention layer
  (``models/attention.py``): ``dude.attention`` — the attention core (the
  Pallas kernels or the jnp scan, or a decode step's attention over its
  cache), not the projections;
* inside ``dude.backward``, in an MoE layer (``models/moe.py``):
  ``dude.moe_route`` — the router, top-k, the sort, the gathers of the
  dispatch and the combine — and ``dude.moe_experts`` — the grouped matmuls
  over the held experts and their activation.

Host spans (``jax.profiler.TraceAnnotation``, via :func:`span`) mark what
the host is doing, on the profiler's clock; their keyword ids are encoded
only while a profiler runs, and a span costs next to nothing when none does:

* ``dude.step`` (``round``) — ``Trainer.step``: mask transfers and dispatch;
* ``dude.arrival`` (``arrival``, ``worker``, ``tau``) — one arrival, with the
  children ``dude.sample`` (the ``sample_fn`` call), ``dude.grad`` (key,
  snapshot unravel, gradient and ravel dispatch), ``dude.commit`` (the
  arrival-step dispatch and the device-side loss EMA), ``dude.queue_wait``
  (the host blocked on the device queue) and ``dude.record`` (a record
  point's syncs);
* ``dude.deliver`` (``arrival``) — handing the new model to a worker.
"""

from __future__ import annotations

import functools

import jax

UNRAVEL = "dude.unravel"
BACKWARD = "dude.backward"
RAVEL = "dude.ravel"
ROUND = "dude.round"
COMMIT = "dude.commit"      # a device scope and a host span
APPLY = "dude.apply"
ATTENTION = "dude.attention"        # nested in BACKWARD
MOE_ROUTE = "dude.moe_route"        # nested in BACKWARD
MOE_EXPERTS = "dude.moe_experts"    # nested in BACKWARD
SCOPES = (UNRAVEL, BACKWARD, RAVEL, ROUND, COMMIT, APPLY, ATTENTION,
          MOE_ROUTE, MOE_EXPERTS)

STEP = "dude.step"
ARRIVAL = "dude.arrival"
SAMPLE = "dude.sample"
GRAD = "dude.grad"
QUEUE_WAIT = "dude.queue_wait"
RECORD = "dude.record"
DELIVER = "dude.deliver"
SPANS = (STEP, ARRIVAL, SAMPLE, GRAD, COMMIT, QUEUE_WAIT, RECORD, DELIVER)


def span(name: str, **ids):
    """A host span ``name`` carrying the integer ``ids`` of its request."""
    return jax.profiler.TraceAnnotation(name, **ids)


def scoped(name: str):
    """Decorator: trace the function under the device scope ``name``,
    keeping its name (so a ``jax.jit`` of it keeps its module name)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
