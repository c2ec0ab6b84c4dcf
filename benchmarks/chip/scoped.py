#!/usr/bin/env python3
"""The program's own names in a traced run: device scopes and host spans.

The program (``src/repro/runtime/spans.py``) tags its device ops with
``dude.*`` scopes, which a TPU profile carries in each op's ``tf_op`` stat,
and puts ``dude.*`` host spans, with integer ids, around its own work.
``tracing.extract`` keeps neither.  ``extract`` here reads them from the
run's ``.xplane.pb`` into a compact trace of the same form, with two lists
added beside the rows: ``op_scopes`` per chip (the scope of each op row,
``""`` where none) and ``host_ids`` (the ids of each host row, ``{}`` for a
benchmark span).  ``Scoped`` reduces it as ``tracing.Reduced`` does and adds
the device time by scope and the self time of host spans.

A metric reader calls ``of(m)``: the scoped reduction of the run's trace,
or ``None`` where the trace carries no program scope or span (a program
that predates them), so that the metric is left out of the result line.

    python3 benchmarks/chip/scoped.py <run.xplane.pb> <cut.json.gz> [rows]

writes a cut of the scoped compact trace, as the tests keep
(``tests/data/scoped.*``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
from pathlib import Path

import tracing

HERE = Path(__file__).resolve().parent
# where run.py's Capture writes each cell's profile
TRACE_DIR = HERE.parents[1] / ".bench_trace"

PROGRAM_SPANS = ("dude.step", "dude.arrival", "dude.sample", "dude.grad",
                 "dude.commit", "dude.queue_wait", "dude.record",
                 "dude.deliver")
SPANS = tracing.SPANS + PROGRAM_SPANS
SCOPES = ("dude.unravel", "dude.backward", "dude.ravel", "dude.round",
          "dude.commit", "dude.apply")
# a scope in an op's HLO op_name (the profile's ``tf_op``)
_SCOPE = re.compile(r"(?:^|/)(dude\.[a-z_]+)(?=[/:]|$)")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------- the xplane wire format

def _varint(b: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """``(field, value)`` of one protobuf message in ``b[i:end]``: a varint's
    value, or a length-delimited field's ``(start, end)``."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, v


def _str(b: bytes, v: tuple) -> str:
    return b[v[0]:v[1]].decode("utf-8", "replace")


def _plane_scopes(b: bytes, start: int, end: int):
    """One ``XPlane``: its name, and the ``dude.*`` scope (or ``""``) of each
    event of its ``XLA Ops`` line in order, read from the ``tf_op`` stat (the
    HLO op_name) of the event's metadata, with a name -> scope map of the
    names whose scope is unambiguous.  ``ProfileData`` does not expose
    event-metadata stats, so this reads the ``XSpace`` wire format
    (``tsl/profiler/protobuf/xplane.proto``)."""
    name, ops_line, stat_names = "", None, {}
    meta = {}                   # event metadata id -> (name, [(stat, val)])
    for f, v in _fields(b, start, end):
        if f == 2:
            name = _str(b, v)
            if not tracing._is_chip_plane(name):  # fields come in order
                return name, None, None
        elif f == 3:            # XLine: name 2, events 4
            ln = next((w for g, w in _fields(b, *v) if g == 2), None)
            if ln is not None and _str(b, ln) == "XLA Ops":
                ops_line = v
        elif f in (4, 5):       # map entry: key 1, value 2
            val = next((w for g, w in _fields(b, *v) if g == 2), None)
            if val is None:
                continue
            if f == 5:          # XStatMetadata: id 1, name 2
                sm = dict(_fields(b, *val))
                stat_names[sm.get(1, 0)] = _str(b, sm[2]) if 2 in sm else ""
                continue
            mid, mname, stats = 0, "", []
            for g, w in _fields(b, *val):   # XEventMetadata: 1 2 5
                if g == 1:
                    mid = w
                elif g == 2:
                    mname = _str(b, w)
                elif g == 5:    # XStat: metadata_id 1, str 5, ref 7
                    st = dict(_fields(b, *w))
                    stats.append((st.get(1, 0), st.get(5), st.get(7)))
            meta[mid] = (mname, stats)
    if ops_line is None:
        return name, None, None
    scope_of = {}
    for mid, (mname, stats) in meta.items():
        scope = ""
        for sid, sv, ref in stats:
            if stat_names.get(sid) != "tf_op":
                continue
            text = _str(b, sv) if sv is not None else stat_names.get(ref, "")
            hit = _SCOPE.search(text)
            scope = hit.group(1) if hit else ""
        scope_of[mid] = scope
    by_name: dict = {}
    for mid, (mname, _) in meta.items():
        by_name.setdefault(mname, set()).add(scope_of[mid])
    by_name = {k: v.pop() for k, v in by_name.items() if len(v) == 1}
    seq = []
    for f, v in _fields(b, *ops_line):
        if f == 4:              # XEvent: metadata_id 1
            mid = next((w for g, w in _fields(b, *v) if g == 1), 0)
            seq.append(scope_of.get(mid, ""))
    return name, seq, by_name


def op_scopes(xplane_path: str) -> dict:
    """Per chip plane name: ``(scopes of the XLA Ops events in order,
    name -> scope)``."""
    with open(xplane_path, "rb") as f:
        b = f.read()
    out = {}
    for field, v in _fields(b, 0, len(b)):
        if field == 1:          # XSpace.planes
            name, seq, by_name = _plane_scopes(b, *v)
            if seq is not None:
                out[name] = (seq, by_name)
    return out


# ------------------------------------------------------- the compact form

def _ids(e) -> dict:
    return {k: int(v) for k, v in e.stats
            if isinstance(v, int) or str(v).lstrip("-").isdigit()}


def extract(xplane_path: str) -> dict:
    """``tracing.extract``'s compact trace, with the program's spans among
    the host rows, and ``op_scopes`` / ``host_ids`` beside the rows."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    scopes = op_scopes(xplane_path)
    chips, host, host_ids = [], [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if tracing._is_chip_plane(plane.name):
            rows = {key: [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in lines[line].events]
                    if line in lines else []
                    for key, line in (("ops", "XLA Ops"),
                                      ("modules", "XLA Modules"))}
            seq, by_name = scopes.get(plane.name, ([], {}))
            if len(seq) != len(rows["ops"]):
                log(f"[scoped] {plane.name}: {len(seq)} op scopes for "
                    f"{len(rows['ops'])} ops; scopes by op name")
                seq = [by_name.get(r[0], "") for r in rows["ops"]]
            rows["op_scopes"] = seq
            chips.append((int(plane.name.rpartition(":")[2]), rows))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in SPANS:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                        host_ids.append(_ids(e)
                                        if e.name in PROGRAM_SPANS else {})
    chips.sort(key=lambda c: c[0])
    return {"chips": [c for _, c in chips], "host": host,
            "host_ids": host_ids}


def cut(compact: dict, max_rows: int) -> dict:
    """``tracing.save_compact``'s cut (the first ``max_rows`` op rows of
    each chip after the window opens), keeping scopes and ids."""
    w0 = max((r for r in compact["host"] if r[0] == "window"),
             key=lambda r: r[2])[1]
    out = {"chips": [], "host": [], "host_ids": []}
    t_end = w0
    for c in compact["chips"]:
        idx = sorted((i for i, r in enumerate(c["ops"]) if r[1] >= w0),
                     key=lambda i: c["ops"][i][1])[:max_rows]
        ops = [c["ops"][i] for i in idx]
        t_end = ops[-1][1] + ops[-1][2] if ops else w0
        out["chips"].append({
            "ops": ops, "op_scopes": [c["op_scopes"][i] for i in idx],
            "modules": [r for r in c["modules"] if w0 <= r[1] < t_end]})
    for r, ids in zip(compact["host"], compact["host_ids"]):
        if r[0] != "window" and w0 <= r[1] < t_end:
            out["host"].append(r)
            out["host_ids"].append(ids)
    out["host"].append(["window", w0, t_end - w0])
    out["host_ids"].append({})
    return out


# -------------------------------------------------------------- reduction

def _intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint ``[start, end]``."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def own_scopes(chip: dict) -> list:
    """The scope each op row of one chip names in its ``op_name``."""
    return chip.get("op_scopes") or [""] * len(chip["ops"])


def attribute(chip: dict) -> list:
    """The scope each op row of one chip counts under: its own, else that
    of the next op in time that has one in the same module run.  XLA adds
    ops with no ``op_name`` (layout copies, zero fills, the pieces of a
    concatenate, fusions rooted at a bitcast, and ``while`` ops, whose
    ``tf_op`` the profile drops) and schedules each just before the ops it
    feeds.  ``""`` after a run's last scoped op and outside module runs."""
    ops = chip["ops"]
    out = list(own_scopes(chip))
    runs = sorted((m[1], m[1] + m[2]) for m in chip["modules"])
    j, run, nxt = len(runs) - 1, None, ""
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]),
                    reverse=True):
        t = ops[i][1]
        while j >= 0 and runs[j][0] > t:
            j -= 1
        here = runs[j] if j >= 0 and t < runs[j][1] else None
        if here != run:
            run, nxt = here, ""
        if out[i]:
            nxt = out[i]
        elif run is not None:
            out[i] = nxt
    return out


class Scoped(tracing.Reduced):
    """``tracing.Reduced`` of a scoped compact trace, with the device time
    by program scope and the self time of program spans."""

    def __init__(self, compact: dict):
        super().__init__(compact)
        self.scopes = [attribute(c) for c in self.chips]

    def has_program(self) -> bool:
        """Whether the trace holds any program scope or span."""
        return any(any(own_scopes(c)) for c in self.chips) or any(
            r[0] in PROGRAM_SPANS for r in self.compact["host"])

    def scope_time(self, names, own: bool = False) -> float:
        """Device seconds under the scopes ``names``: the union of the
        intervals of their ops inside the window, so that a container op
        and the ops of its body count once; averaged over the chips.  An
        op counts under the scope ``attribute`` gives it, or with ``own``
        only under the scope its ``op_name`` names."""
        names, tot = set(names), 0.0
        for c, attributed in zip(self.chips, self.scopes):
            scopes = own_scopes(c) if own else attributed
            rows = [r for r, sc in zip(c["ops"], scopes) if sc in names]
            tot += _length(tracing._union(self._clip(rows)))
        return tot / len(self.chips) * 1e-9

    def host_count(self, names) -> int:
        """Host spans named in ``names`` that start in the window."""
        return sum(1 for r in self.compact["host"]
                   if r[0] in names and self.w0 <= r[1] < self.w1)

    def host_time(self, names) -> float:
        """Self seconds of the host spans ``names`` inside the window: each
        span's time less that of the spans that lie inside it."""
        rows = sorted((r for r in self.compact["host"] if r[0] != "window"),
                      key=lambda r: (r[1], -r[2]))
        tot = 0.0
        for i, r in enumerate(rows):
            if r[0] not in names:
                continue
            own = self._clip([r])
            if not own:
                continue
            end = r[1] + r[2]
            inner = []
            for q in rows[i + 1:]:
                if q[1] > end:
                    break
                if q[1] + q[2] <= end:
                    inner.append(q)
            kids = _intersect(tracing._union(self._clip(inner)), own)
            tot += _length(own) - _length(kids)
        return tot * 1e-9

    def top_unscoped(self, k: int = 5) -> list:
        """The longest ops in the window whose ``op_name`` names no scope
        (container ops left out), by short name."""
        tot: dict = {}
        for c in self.chips:
            for r, sc in zip(c["ops"], own_scopes(c)):
                if sc or tracing._CONTAINER.search(r[0].split(" = ", 1)[-1]):
                    continue
                for s, e in self._clip([r]):
                    name = tracing.short_name(r[0])
                    tot[name] = tot.get(name, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(self.chips) * 1e-9] for n, v in top]

    def summary(self) -> str:
        """Device time by scope, the unscoped share of the busy time, and
        the share of idle time that a program span labels."""
        scoped = self.scope_time(SCOPES)
        own = self.scope_time(SCOPES, own=True)
        idle = self.idle_by_label()
        named = sum(v for k, v in idle.items() if k in PROGRAM_SPANS)
        busy = self.busy_s or float("nan")
        return (f"device s by program scope "
                f"{ {k: self.scope_time((k,)) for k in SCOPES} }; unscoped "
                f"{busy - scoped:.4f} s of busy {busy:.4f} s "
                f"({100 * (1 - scoped / busy):.2f} %; "
                f"{100 * (1 - own / busy):.2f} % without an op_name scope); "
                f"longest ops without one {self.top_unscoped(5)}; idle by "
                f"span {idle}; {100 * named / (sum(idle.values()) or 1):.2f} "
                f"% of idle under program spans")


# ------------------------------------------------------------- the reader

_LOADED: dict = {}


def _newest_xplane():
    files = glob.glob(str(TRACE_DIR / "*" / "plugins" / "profile" / "*" /
                      "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _load(red) -> "Scoped | None":
    """The scoped reduction of the newest profile, if it is the one ``red``
    was reduced from (the same window span) and names the program's work."""
    path = _newest_xplane()
    if path is None:
        return None
    try:
        sc = Scoped(extract(path))
    except (ValueError, IndexError, KeyError, OSError) as e:
        log(f"[scoped] {path}: not read ({type(e).__name__}: {e})")
        return None
    if (sc.w0, sc.w1) != (red.w0, red.w1):
        log(f"[scoped] {path}: another window than the run's; not read")
        return None
    if not sc.has_program():
        log("[scoped] the trace holds no program scope or span")
        return None
    log(f"[scoped] {sc.summary()}")
    return sc


def of(m) -> "Scoped | None":
    """The scoped reduction of the run a metric reader is given (``m``,
    run.py's ``MetricInput``): ``m.trace`` itself where it is one, else
    read once per run from the profile it came from; ``None`` where the
    program names none of its work."""
    red = m.trace
    if isinstance(red, Scoped):
        return red if red.has_program() else None
    key = (red.w0, red.w1)
    if key not in _LOADED:
        _LOADED[key] = _load(red)
    return _LOADED[key]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        return 2
    rows = int(argv[2]) if len(argv) == 3 else 4000
    with gzip.open(argv[1], "wt") as f:
        json.dump(cut(extract(argv[0]), rows), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
