"""What every mode shares: the run's context, the program's session, and
the readings the check compares (losses and per-leaf norms).

A mode (``modes/<name>.py``, named by the traffic mix) builds one
``Trainer`` through ``repro.api``, drives its first steps from the seed
(the readings), runs the measured window on the same object, frees it, and
then runs the configuration's plain reference over the same inputs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any

import numpy as np

HERE = Path(__file__).resolve().parent


def load_module(path: Path, name: str):
    """Import the file at ``path`` (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


@dataclasses.dataclass
class Run:
    """One run of one cell."""

    workload: str
    cfg: dict            # configs/<config>.json
    mix: dict            # traffic/<traffic>.json
    seed: int
    seconds: float
    ref: Any             # configs/<family>.py, the plain reference
    prog: Any            # configs/<family>_program.py, the program adapter
    capture: Any         # tracing.Capture
    traffic: Any         # traffic.Traffic
    keys: dict           # traffic.seeds(seed)

    @property
    def lr(self) -> float:
        return float(self.cfg["run"]["lr"])


def load_family(cfg: dict):
    fam = cfg["family"]
    ref = load_module(HERE / "configs" / f"{fam}.py", f"ref_{fam}")
    prog = load_module(HERE / "configs" / f"{fam}_program.py",
                       f"prog_{fam}")
    return ref, prog


def make_mesh(run: Run):
    """The mix's device mesh (``"mesh": {"shape", "axes"}``), or None for a
    one-chip mix."""
    spec = run.mix.get("mesh")
    if spec is None:
        return None
    import jax
    from repro.launch.mesh import make_mesh as program_mesh
    k = int(np.prod(spec["shape"]))
    return program_mesh(spec["shape"], spec["axes"], devices=jax.devices()[:k])


def trainer_config(run: Run, n_workers: int, mesh=None):
    """The program's ``TrainerConfig`` for this run; on a mesh, with the
    mix's ``params_layout``."""
    import jax.numpy as jnp
    from repro.api import TrainerConfig
    r = run.cfg["run"]
    layout = run.mix["mesh"]["params_layout"] if mesh is not None \
        else "replicated"
    return TrainerConfig(
        arch=run.prog.model_config(run.cfg, n_workers), algo="dude",
        optimizer=r["optimizer"], lr=float(r["lr"]),
        server_backend=r["server_backend"],
        grad_dtype=jnp.dtype(r["grad_dtype"]),
        commit_format=r["commit_format"], mesh=mesh, params_layout=layout,
        seed=run.keys["program"])


def weight_key(run: Run):
    import jax
    return jax.random.PRNGKey(run.keys["weights"])


def program_weights(run: Run):
    """The benchmark's weights in the program's layout, made on the device
    in one jitted call from the seed."""
    import jax
    cfg, ref, prog = run.cfg, run.ref, run.prog
    return jax.jit(lambda k: prog.to_program(ref.init(k, cfg)))(
        weight_key(run))


class LeafNorms:
    """Per-leaf sums of squares of a flat ``[P]`` vector laid out as the
    program lays out its parameters (leaves in flatten order, then zero
    padding), and of its difference from the initial weights, which are made
    again from the key inside the same program rather than kept."""

    def __init__(self, run: Run, padded_size: int):
        import jax
        import jax.numpy as jnp
        cfg, ref, prog = run.cfg, run.ref, run.prog
        shapes = jax.eval_shape(lambda k: prog.to_program(ref.init(k, cfg)),
                                weight_key(run))
        sizes = [int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)]
        offs = np.concatenate([[0], np.cumsum(sizes)[:-1]]).tolist()
        if sum(sizes) > padded_size:
            raise ValueError(f"layout: {sum(sizes)} weights > flat vector "
                             f"of {padded_size}")
        self.segs = list(zip(offs, sizes))

        def sq(flat):
            return jnp.stack([jnp.sum(jnp.square(flat[o:o + n]))
                              for o, n in self.segs])

        def change_sq(flat, key):
            w0 = jax.tree.leaves(prog.to_program(ref.init(key, cfg)))
            return jnp.stack([jnp.sum(jnp.square(flat[o:o + n]
                                                 - w.reshape(-1)))
                              for (o, n), w in zip(self.segs, w0)])

        self.sq = jax.jit(sq)
        self._change = jax.jit(change_sq)
        self.key = weight_key(run)

    def change_sq(self, flat):
        return self._change(flat, self.key)


def tree_leaf_sq(tree) -> np.ndarray:
    """Per-leaf sums of squares of a program-layout tree (reference side)."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t)]))(tree),
        np.float64)


def readings(losses, grad_sq, change_sq) -> dict:
    return {"losses": [float(x) for x in losses],
            "grad_sq": np.asarray(grad_sq, np.float64).tolist(),
            "change_sq": np.asarray(change_sq, np.float64).tolist()}
