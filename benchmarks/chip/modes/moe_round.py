"""MoE round mode: the round mode (``modes/round.py``, loaded as it is),
with the program's MoE step counters read over the window.

Each round's metrics carry ``moe_rows_held`` (the (token, slot) pairs routed
to the experts this chip holds, over the workers and layers) and
``moe_rows_dropped``.  The window keeps each round's pair of device scalars
as the step returns them and reads them once, after the window's clock has
stopped: their sums go into the window's readings, and a round that
dropped a pair counts as failed.  The window's readings also carry the
cell's configuration and mix (``cfg``, ``mix``), from which the MoE
metrics count the work of those pairs (``moe_counts.py``), and the length
of the program's flat parameter state (``param_count``), from which the
round kernel's roofline counts its bytes.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

import harness

_round = harness.load_module(Path(__file__).resolve().parent / "round.py",
                             "mode_round")


class Mode(_round.Mode):

    def setup(self) -> None:
        super().setup()
        step = self.trainer.step
        self.counters = []

        def counted(*args, **kwargs):
            m = step(*args, **kwargs)
            self.counters.append((m["moe_rows_held"], m["moe_rows_dropped"]))
            return m

        self.trainer.step = counted

    def window(self) -> dict:
        import jax
        self.counters = []
        win = super().window()
        held, dropped = np.asarray(jax.device_get(self.counters),
                                   np.float64).reshape(-1, 2).T
        self.counters = []
        win["moe_rows_held"] = float(held.sum())
        win["moe_rows_dropped"] = float(dropped.sum())
        win["failed"] += int(np.count_nonzero(dropped))
        win["cfg"], win["mix"] = self.run.cfg, self.run.mix
        win["param_count"] = int(self.trainer.state.params.shape[0])
        print(f"[moe_round] window: {held.size} rounds, "
              f"{win['moe_rows_held']:.0f} pairs to held experts "
              f"({held.mean():.1f} a round), "
              f"{win['moe_rows_dropped']:.0f} dropped", flush=True)
        return win
