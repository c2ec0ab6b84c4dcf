"""The program's scopes and spans in a trace (``scoped.py``) and the
per-layer metrics that read them: the xplane reader on a hand-written
profile, the union and self-time rules on hand-made compact traces, and
the metrics on cuts recorded on the chip (``tests/data``).  The first six
metrics read as they always did, and the new ones read nothing, where the
program names none of its work.
"""

import gzip
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import scoped  # noqa: E402
import tracing  # noqa: E402

DATA = HERE / "tests" / "data"
BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
# what each cell's window gave the host-clock metrics (mfu reads no trace)
WIN = {"round": {"tokens": 491520, "seconds": 20.048},
       "arrival": {"tokens": 287744, "seconds": 19.97}}
# the first six metrics on the cuts recorded before the program named its
# work, as the accepted reducer reads them
UNSCOPED = {
    "qwen2-0.5b.round.n8.json.gz": {
        "mfu.round": 14.89267547378272,
        "idle_share.round": 0.0325809105643952,
        "round_kernel_roofline.round": 81.93033228783281},
    "qwen2-0.5b.arrival.n4.json.gz": {
        "mfu.arrival": 8.752473352679987,
        "idle_share.arrival": 2.275315090447404,
        "commit_ms.arrival": 29.05392},
}
# cuts recorded with the program's names (one v5e): 1.7 rounds, 10 arrivals
SCOPED = {
    "scoped.qwen2-0.5b.round.n8.json.gz": ("backward_ms.round",
                                         "flatten_ms.round"),
    "scoped.qwen2-0.5b.arrival.n4.json.gz": (
        "backward_ms.arrival", "flatten_ms.arrival",
        "queue_wait_ms.arrival", "record_wait_ms.arrival"),
}


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def read_metrics(name, red):
    """``run.per_layer`` on the reduction ``red`` of a cut of the cell that
    ``name`` names, as a traced run of that cell."""
    import counts
    import harness
    import run
    cell = next(w for w in BENCH["workloads"] if w["name"] in name)
    cfg = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    mix = harness.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    kind = "arrival" if "arrival" in cell["name"] else "round"
    m = run.MetricInput(kind, red, WIN[kind], cfg, mix, 1,
                        counts.peaks("TPU v5 lite"))
    return {k: v["value"]
            for k, v in run.per_layer(BENCH, cell["name"], m).items()}


@pytest.mark.parametrize("name", sorted(UNSCOPED))
def test_first_metrics_read_as_before(name):
    """Cuts with no program scope or span: the six first metrics read to
    the last digit what the accepted reducer reads, the new ones nothing."""
    assert read_metrics(name, scoped.Scoped(load(DATA / name))) == \
        UNSCOPED[name]


@pytest.mark.parametrize("name", sorted(UNSCOPED))
def test_no_profile_reads_no_scope_metric(name, tmp_path, monkeypatch):
    """A run's plain reduction with no profile to read: the new metrics are
    left out and nothing raises."""
    monkeypatch.setattr(scoped, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scoped, "_LOADED", {})
    red = tracing.Reduced(load(DATA / name))
    assert read_metrics(name, red) == UNSCOPED[name]


def test_scope_time_is_a_union():
    """A container op and the ops of its body count once; an op with no
    scope of its own counts under the next scoped op of its module run, or
    under none with ``own``."""
    ops = [["%while.1 = (f32[]) while(%t)", 10, 50, "dude.backward"],
           ["dot.1", 12, 10, "dude.backward"],
           ["dot.2", 30, 25, "dude.backward"],     # runs past the container
           ["copy", 70, 5, ""],
           ["legacy", 80, 5, ""],
           ["concat", 90, 20, "dude.ravel"],       # half outside the window
           ["slice", 0, 4, "dude.unravel"]]        # before the window
    trace = {"chips": [{"ops": [r[:3] for r in ops],
                        "op_scopes": [r[3] for r in ops],
                        "modules": [["jit_flat_train_step(1)", 5, 100]]}],
             "host": [["window", 5, 95]], "host_ids": [{}]}
    red = scoped.Scoped(trace)
    assert red.scope_time(["dude.backward"]) == pytest.approx(50e-9)
    assert red.scope_time(["dude.ravel"], own=True) == pytest.approx(10e-9)
    # the copy and the legacy row run before the concat in the same step
    assert red.scope_time(["dude.ravel"]) == pytest.approx(20e-9)
    assert red.scope_time(["dude.unravel"]) == 0
    assert red.scope_time(["dude.backward", "dude.ravel"], own=True) == \
        pytest.approx(60e-9)
    assert red.scope_time(scoped.SCOPES) == pytest.approx(red.busy_s)
    assert red.busy_s == pytest.approx(70e-9)
    assert red.op_time(lambda n: n == "legacy") == pytest.approx(5e-9)
    assert red.top_ops(1)[0][0] == "dot.2"
    assert [n for n, _ in red.top_unscoped(5)] == ["copy", "legacy"]
    assert red.scopes[0] == ["dude.backward"] * 3 + ["dude.ravel"] * 3 + \
        ["dude.unravel"]


def test_host_time_is_self_time():
    """A span's time less that of the spans inside it, cut to the window."""
    host = [["window", 0, 1000],
            ["run_async", 1, 998],
            ["dude.arrival", 100, 300],
            ["dude.grad", 110, 100],
            ["dude.sample", 120, 30],
            ["sample", 125, 10],
            ["dude.commit", 220, 50],
            ["dude.queue_wait", 280, 100],
            ["dude.record", 950, 100],            # runs past the end
            ["dude.arrival", 940, 200]]
    trace = {"chips": [{"ops": [["x", 0, 1]], "modules": []}],
             "host": host, "host_ids": [{}] * len(host)}
    red = scoped.Scoped(trace)
    assert red.host_time(["dude.grad"]) == pytest.approx(70e-9)
    assert red.host_time(["dude.sample"]) == pytest.approx(20e-9)
    assert red.host_time(["dude.queue_wait"]) == pytest.approx(100e-9)
    assert red.host_time(["dude.arrival"]) == pytest.approx(
        (300 - 100 - 50 - 100 + 60 - 50) * 1e-9)
    assert red.host_time(["dude.record"]) == pytest.approx(50e-9)
    assert red.host_count(["dude.arrival"]) == 2
    assert red.host_count(["dude.record"]) == 1
    assert red.label(130) == "sample"
    assert red.label(300) == "dude.queue_wait"
    assert red.label(500) == "run_async"


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_scope_metrics_read_the_program_names(name):
    """Every new metric of the cell reads a value off its cut, the first
    ones read too, and the parts of a round or an arrival fit in it."""
    got = read_metrics(name, scoped.Scoped(load(DATA / name)))
    kind = "arrival" if "arrival" in name else "round"
    assert set(SCOPED[name]) <= set(got)
    first = "qwen2-0.5b.%s.json.gz" % (
        "arrival.n4" if kind == "arrival" else "round.n8")
    assert set(UNSCOPED[first]) <= set(got)
    if kind == "round":
        # per step: backward + layout changes + kernel within one step
        assert 200 < got["backward_ms.round"] < 334
        assert 0 < got["flatten_ms.round"] < 50
    else:
        period = 1e3 / 14.1     # ms per arrival on the chip
        assert 0 < got["backward_ms.arrival"] + got["flatten_ms.arrival"] \
            + got["commit_ms.arrival"] < period
        assert 0 <= got["queue_wait_ms.arrival"] < period
        # the cut ends inside its one record point
        assert 0 < got["record_wait_ms.arrival"] < 4 * period


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_every_scoped_op_counts_once(name):
    """On a chip cut: the scopes split the busy time (no op under two),
    the attributed scopes cover what the op_names cover and more, and an
    op keeps its own scope."""
    red = scoped.Scoped(load(DATA / name))
    parts = sum(red.scope_time((k,)) for k in scoped.SCOPES)
    assert parts == pytest.approx(red.scope_time(scoped.SCOPES), rel=1e-3)
    assert red.scope_time(scoped.SCOPES, own=True) <= \
        red.scope_time(scoped.SCOPES) <= red.busy_s + 1e-9
    for c, scopes in zip(red.chips, red.scopes):
        assert all(sc == own for own, sc in zip(c["op_scopes"], scopes)
                   if own)


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_cut_keeps_scopes_and_ids(name):
    """A shorter cut of a cut keeps each row's scope and ids beside it, and
    the accepted reducer reads it."""
    full = load(DATA / name)
    part = scoped.cut(full, 500)
    ops = full["chips"][0]["ops"]
    scope = {tuple(r): s for r, s in zip(ops, full["chips"][0]["op_scopes"])}
    c = part["chips"][0]
    assert len(c["ops"]) == 500 == len(c["op_scopes"])
    assert all(scope[tuple(r)] == s for r, s in zip(c["ops"], c["op_scopes"]))
    ids = {tuple(r): i for r, i in zip(full["host"], full["host_ids"])}
    assert len(part["host"]) == len(part["host_ids"])
    assert all(ids[tuple(r)] == i for r, i in
               zip(part["host"], part["host_ids"]) if r[0] != "window")
    assert tracing.Reduced(part).busy_s > 0


# a profile as a TPU run writes it: a module run of three ops, two of them
# tagged by the program, and one program span with its ids
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 40000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 60000 duration_ps: 30000 } }
  event_metadata { key: 1 value { id: 1 name: "%copy.1 = f32[2] copy(%a)" } }
  event_metadata { key: 2 value { id: 2 name: "%dot.1 = f32[2] dot(%a, %b)"
    stats { metadata_id: 7
            str_value: "jit(f)/jit(main)/dude.backward/dot_general" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[2] fusion(%c)"
    stats { metadata_id: 7
            str_value: "jit(f)/jit(main)/dude.ravel/concatenate" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_flat_train_step(1)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 50000
      stats { metadata_id: 3 int64_value: 4 }
      stats { metadata_id: 4 int64_value: 2 } }
    events { metadata_id: 5 offset_ps: 6000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "dude.arrival" } }
  event_metadata { key: 5 value { id: 5 name: "other" } }
  stat_metadata { key: 3 value { id: 3 name: "arrival" } }
  stat_metadata { key: 4 value { id: 4 name: "worker" } }
}
"""


def write_profile(root, text, run="r1"):
    from jax.profiler import ProfileData
    d = root / "cell" / "plugins" / "profile" / run
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_extract_reads_scopes_and_ids(tmp_path):
    """The xplane reader finds each op's scope in its metadata's ``tf_op``
    and each program span's ids; the rows are those ``tracing.extract``
    gives, and the unnamed copy counts under the scope of the op after
    it."""
    path = write_profile(tmp_path, XSPACE)
    c = scoped.extract(str(path))
    plain = tracing.extract(str(path))
    assert c["chips"][0]["ops"] == plain["chips"][0]["ops"]
    assert c["chips"][0]["op_scopes"] == ["", "dude.backward", "dude.ravel"]
    assert c["host"] == [["window", 1000.0, 100.0],
                         ["dude.arrival", 1005.0, 50.0]]
    assert c["host_ids"] == [{}, {"arrival": 4, "worker": 2}]
    red = scoped.Scoped(c)
    assert red.scope_time(["dude.backward"]) == pytest.approx(30e-9)
    assert red.scope_time(["dude.backward"], own=True) == pytest.approx(10e-9)
    assert red.host_count(["dude.arrival"]) == 1


def test_of_reads_the_runs_own_profile(tmp_path, monkeypatch):
    """``of`` reads the newest profile once, and only if its window is the
    run's and it names the program's work."""
    monkeypatch.setattr(scoped, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scoped, "_LOADED", {})
    path = write_profile(tmp_path, XSPACE)

    class M:
        trace = tracing.Reduced(tracing.extract(str(path)))

    got = scoped.of(M)
    assert isinstance(got, scoped.Scoped) and (got.w0, got.w1) == \
        (M.trace.w0, M.trace.w1)
    assert scoped.of(M) is got
    # another run's window
    M.trace = tracing.Reduced({"chips": [{"ops": [["x", 0, 1]],
                                          "modules": []}],
                               "host": [["window", 0, 5]]})
    assert scoped.of(M) is None
    # a program that names none of its work
    bare = XSPACE.replace("dude.", "jax.")
    path = write_profile(tmp_path, bare, run="r2")
    M.trace = tracing.Reduced(tracing.extract(str(path)))
    monkeypatch.setattr(scoped, "_LOADED", {})
    assert scoped.of(M) is None
