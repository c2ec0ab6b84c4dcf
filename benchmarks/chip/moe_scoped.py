"""The program's scopes in a traced run, each op counted under its
innermost ``dude.*`` scope.

``scoped.py`` counts an op under the first ``dude.*`` name of its op_name,
the layer scope; the MoE scopes (``dude.moe_route``, ``dude.moe_experts``)
nest inside ``dude.backward``, so there they are invisible.  This module
loads a second copy of ``scoped.py``, unedited, with two changes to that
copy: the pattern its xplane reader applies to an op's ``tf_op`` finds the
last ``dude.*`` name instead of the first, and the grouped-matmul kernels
count under ``dude.moe_experts``.  The TPU compiler lowers each
``ragged_dot`` to a kernel of its own (``%ragged-dot-*``, a
``tpu_custom_call``) whose event carries no op_name, so the scope of the
next op in time would otherwise take it; the program has no other ragged
dot.  The wire-format reader, the attribution of the other unscoped ops
and the reduction are ``scoped.py``'s own.

A metric reader calls ``of(m)``, as with ``scoped.of``: ``None`` where the
trace holds no program scope or span.
"""

from __future__ import annotations

import re
from pathlib import Path

import harness

# a dude.* name after which the op_name has no other
INNERMOST = re.compile(r"(?:^|/)(dude\.[a-z_]+)(?=[/:]|$)"
                       r"(?!.*/dude\.[a-z_]+(?:[/:]|$))")

ROUTE = "dude.moe_route"
EXPERTS = "dude.moe_experts"
STEP_MODULE = "jit_flat_train_step("
KERNEL = re.compile(r"^%ragged-dot[\w.-]* = ")

inner = harness.load_module(Path(__file__).resolve().parent / "scoped.py",
                            "scoped_innermost")
inner._SCOPE = INNERMOST
_extract = inner.extract


def mark_kernels(compact: dict) -> dict:
    """Put the grouped-matmul kernels of a scoped compact trace under
    ``dude.moe_experts``."""
    for chip in compact["chips"]:
        chip["op_scopes"] = [EXPERTS if KERNEL.match(r[0]) else sc
                             for r, sc in zip(chip["ops"],
                                              chip["op_scopes"])]
    return compact


def extract(xplane_path: str) -> dict:
    """The copy's compact trace, with the kernels marked."""
    return mark_kernels(_extract(xplane_path))


inner.extract = extract
of = inner.of


def per_round_ms(m, scopes):
    """Device milliseconds per round step under ``scopes`` (innermost),
    or None where the program tags no op with them."""
    if m.kind != "round":
        return None
    trace = of(m)
    if trace is None:
        return None
    steps = trace.count("modules", lambda name: name.startswith(STEP_MODULE))
    t = trace.scope_time(scopes)
    if steps == 0 or t <= 0:
        return None
    return 1e3 * t / steps
