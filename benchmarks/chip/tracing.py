"""Profiler capture and the reduction from trace to numbers.

Capture: ``Capture`` starts the JAX profiler around the measured window and
puts ``jax.profiler.TraceAnnotation`` spans around the benchmark's own calls
(``window``, ``dispatch``, ``wait``, ``sample``, ``run_async``).  Without
``--trace 1`` the spans are no-ops.

Reduction works on a compact form of the trace (``extract``): per chip the
device's ``XLA Ops`` and ``XLA Modules`` events, and the host spans above,
as ``[name, start_ns, duration_ns]`` rows on the profiler's one clock.
``Reduced`` then gives the window, the busy time (the union of the op
intervals inside the window), the idle gaps labelled with the innermost
host span that covers them, and the device time of ops and modules by name.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import re
import sys

SPANS = ("window", "run_async", "dispatch", "wait", "sample")
# ops that only contain other ops of the same line (their bodies' ops are
# listed too): left out of the breakdown, harmless to the busy union
_CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``; a Pallas
    kernel keeps its custom-call target."""
    head = op.split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in op:
        head += " tpu_custom_call"
    return head


class Capture:
    """Profiler on/off around a window, and the benchmark's host spans."""

    def __init__(self, enabled: bool, log_dir: str):
        self.enabled = enabled
        self.log_dir = log_dir

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def trace(self):
        if not self.enabled:
            yield
            return
        import jax
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        try:
            with self.span("window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def newest(self) -> str:
        files = glob.glob(os.path.join(self.log_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return max(files, key=os.path.getmtime)


def _is_chip_plane(name: str) -> bool:
    head, _, idx = name.rpartition(":")
    return head == "/device:TPU" and idx.isdigit()


def extract(xplane_path: str) -> dict:
    """The compact trace: ``{"chips": [{"ops": rows, "modules": rows}],
    "host": rows}`` with rows ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    chips, host = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if _is_chip_plane(plane.name):
            chips.append((int(plane.name.rpartition(":")[2]), {
                key: [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in lines[line].events] if line in lines else []
                for key, line in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in ln.events if e.name in SPANS]
        print(f"[trace] plane {plane.name}: lines "
              f"{sorted(lines)[:12]}", file=sys.stderr)
    chips.sort(key=lambda c: c[0])
    return {"chips": [c for _, c in chips], "host": host}


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduced:
    """Numbers from one compact trace, inside its ``window`` span."""

    def __init__(self, compact: dict):
        self.compact = compact
        wins = [r for r in compact["host"] if r[0] == "window"]
        if not wins:
            raise ValueError("trace has no 'window' span")
        _, s, d = max(wins, key=lambda r: r[2])
        self.w0, self.w1 = s, s + d
        self.chips = compact["chips"]
        if not self.chips:
            raise ValueError("trace has no TPU device plane")
        self.busy = [self._union_ops(c["ops"]) for c in self.chips]

    def _clip(self, rows: list) -> list:
        return [[max(s, self.w0), min(s + d, self.w1)] for _, s, d in rows
                if s + d > self.w0 and s < self.w1]

    def _union_ops(self, rows: list) -> list:
        return _union(self._clip(rows))

    # -------------------------------------------------------- totals

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the chips."""
        tot = sum(e - s for u in self.busy for s, e in u)
        return tot / len(self.busy) * 1e-9

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def _time(self, kind: str, match) -> float:
        """Seconds of ``kind`` events whose name satisfies ``match``, inside
        the window, averaged over the chips."""
        tot = 0.0
        for c in self.chips:
            tot += sum(e - s for s, e in
                       self._clip([r for r in c[kind] if match(r[0])]))
        return tot / len(self.chips) * 1e-9

    def op_time(self, match) -> float:
        return self._time("ops", match)

    def module_time(self, match) -> float:
        return self._time("modules", match)

    def count(self, kind: str, match) -> float:
        """Events of ``kind`` matching, that start in the window, per chip."""
        n = sum(1 for c in self.chips for r in c[kind]
                if match(r[0]) and self.w0 <= r[1] < self.w1)
        return n / len(self.chips)

    # ----------------------------------------------------- breakdown

    def top_ops(self, k: int = 10) -> list:
        """The device ops that took most time in the window (container
        ops such as a ``while`` left out), by short name."""
        tot: dict = {}
        for c in self.chips:
            for r in c["ops"]:
                if _CONTAINER.search(r[0].split(" = ", 1)[-1]):
                    continue
                for s, e in self._clip([r]):
                    name = short_name(r[0])
                    tot[name] = tot.get(name, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(self.chips) * 1e-9] for n, v in top]

    def gaps(self) -> list:
        """Idle intervals of chip 0 inside the window: ``[start, end]``."""
        out, t = [], self.w0
        for s, e in self.busy[0]:
            if s > t:
                out.append([t, s])
            t = max(t, e)
        if t < self.w1:
            out.append([t, self.w1])
        return out

    def label(self, t: float) -> str:
        """The innermost benchmark span that covers time ``t``."""
        best = None
        for name, s, d in self.compact["host"]:
            if s <= t <= s + d and (best is None or d < best[1]):
                best = (name, d)
        if best is None or best[0] == "window":
            return "host_other"
        return best[0]

    def top_gaps(self, k: int = 10) -> list:
        g = sorted(self.gaps(), key=lambda x: x[0] - x[1])[:k]
        return [[self.label((s + e) / 2), (e - s) * 1e-9] for s, e in g]

    def idle_by_label(self) -> dict:
        out: dict = {}
        for s, e in self.gaps():
            lab = self.label((s + e) / 2)
            out[lab] = out.get(lab, 0.0) + (e - s) * 1e-9
        return out


def save_compact(compact: dict, path: str, max_rows: int) -> None:
    """A cut of the compact trace small enough to keep: the first
    ``max_rows`` device rows of each kind after the window opens."""
    import gzip
    wins = [r for r in compact["host"] if r[0] == "window"]
    w0 = max(wins, key=lambda r: r[2])[1]
    cut = {"chips": [], "host": []}
    t_end = None
    for c in compact["chips"]:
        ops = sorted((r for r in c["ops"] if r[1] >= w0), key=lambda r: r[1])
        ops = ops[:max_rows]
        t_end = ops[-1][1] + ops[-1][2] if ops else w0
        mods = [r for r in c["modules"] if w0 <= r[1] < t_end]
        cut["chips"].append({"ops": ops, "modules": mods})
    cut["host"] = [r for r in compact["host"]
                   if r[0] != "window" and w0 <= r[1] < t_end]
    cut["host"].append(["window", w0, t_end - w0])
    with gzip.open(path, "wt") as f:
        json.dump(cut, f)
