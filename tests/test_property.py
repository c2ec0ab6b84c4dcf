"""Hypothesis property tests on system invariants.

``hypothesis`` is an optional dev dependency (``pip install .[dev]``); the
whole module is skipped when it is absent so the tier-1 suite stays green.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (
    DuDeConfig, dude_commit, dude_init, dude_round,
    make_round_schedule, truncated_normal_speeds,
)
from repro.core.compression import (
    CommitCodec, dequantize, quantize, topk_mask,
)
from repro.data import dirichlet_partition, label_distribution
from repro.launch.mesh import make_mesh

SET = settings(max_examples=25, deadline=None)


@SET
@given(
    n=st.integers(2, 6),
    steps=st.integers(1, 30),
    seed=st.integers(0, 10_000),
)
def test_incremental_aggregation_identity(n, steps, seed):
    """For ANY commit sequence, g_bar == mean of last-committed gradients."""
    rng = np.random.default_rng(seed)
    cfg = DuDeConfig(n_workers=n)
    like = {"w": jnp.zeros(3)}
    stt = dude_init(like, cfg)
    stored = [jax.tree.map(jnp.zeros_like, like) for _ in range(n)]
    for _ in range(steps):
        i = int(rng.integers(n))
        g = {"w": jnp.asarray(rng.normal(size=3), jnp.float32)}
        stt, gbar = dude_commit(stt, jnp.int32(i), g, cfg)
        stored[i] = g
    full = sum(np.asarray(s["w"]) for s in stored) / n
    np.testing.assert_allclose(np.asarray(gbar["w"]), full, atol=1e-4)


@SET
@given(
    n=st.integers(2, 8),
    std=st.floats(0.1, 5.0),
    rounds=st.integers(5, 60),
    seed=st.integers(0, 10_000),
)
def test_schedule_validity(n, std, rounds, seed):
    """Round schedules: jobs tile time with duration >= 1; a commit at r
    implies a start at r - duration; no worker has two open jobs."""
    speeds = truncated_normal_speeds(n, std=std, seed=seed)
    sch = make_round_schedule(speeds, rounds)
    assert sch.start.shape == (rounds, n)
    open_job = np.zeros(n, bool)
    start_at = np.full(n, -1)
    for r in range(rounds):
        for i in range(n):
            if sch.commit[r, i]:
                assert open_job[i]
                assert r - start_at[i] == sch.duration[i] >= 1
                open_job[i] = False
            if sch.start[r, i]:
                assert not open_job[i]
                open_job[i] = True
                start_at[i] = r


@SET
@given(
    n=st.integers(2, 10),
    alpha=st.floats(0.02, 10.0),
    seed=st.integers(0, 1000),
)
def test_dirichlet_partition_valid(n, alpha, seed):
    """Every index assigned exactly once; every worker non-empty; lower alpha
    => more skew (checked in aggregate elsewhere)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=500)
    shards = dirichlet_partition(labels, n, alpha, seed=seed)
    allidx = np.sort(np.concatenate(shards))
    np.testing.assert_array_equal(allidx, np.arange(500))
    assert all(len(s) >= 1 for s in shards)
    dist = label_distribution(labels, shards)
    np.testing.assert_allclose(dist.sum(axis=1), 1.0, atol=1e-6)


@SET
@given(
    tiles=st.integers(1, 4),
    scale=st.floats(1e-3, 1e3),
    seed=st.integers(0, 1000),
)
def test_quantize_bounded_error(tiles, scale, seed):
    """Tiled int8 quantization error is bounded PER 128-lane TILE: each
    lane's error <= its own tile's scale/2 (plus rounding slack), so a
    large-magnitude tile cannot degrade a small-magnitude one."""
    P = tiles * 128
    x = jnp.asarray(
        np.random.default_rng(seed).normal(size=P) * scale, jnp.float32
    )
    q, s = quantize(x)
    assert q.dtype == jnp.int8 and s.shape == (tiles,)
    err = jnp.abs(dequantize(q, s) - x).reshape(tiles, 128)
    codec = CommitCodec(format="int8_ef")
    bound = codec.quant_bound(x)            # per-tile [T] bound
    assert bound.shape == (tiles,)
    assert bool(jnp.all(jnp.max(err, axis=-1) <= bound))
    # the bound is genuinely per-tile: the pow2 scale sits in
    # [max/127, 2*max/127), so the bound tracks each tile's own max
    raw = np.maximum(np.max(np.abs(np.asarray(x)).reshape(tiles, 128),
                            axis=-1), 1e-12) / 127.0
    b = np.asarray(bound)
    assert (b >= 0.5 * raw).all() and (b <= raw * 1.001).all()
    # scales are exact powers of two (the exactness ingredient)
    assert (np.asarray(s) == np.exp2(np.round(np.log2(np.asarray(s))))).all()


@SET
@given(seed=st.integers(0, 1000), steps=st.integers(1, 20))
def test_error_feedback_telescopes(seed, steps):
    """Sum of EF-decoded commits + final residual == sum of true values
    BITWISE (the Sterbenz-exactness identity dec + ef' == x + ef holds per
    step, so the telescoped sums match to f32 accumulation roundoff)."""
    codec = CommitCodec(format="int8_ef")
    rng = np.random.default_rng(seed)
    ef = jnp.zeros(128)
    total_true = jnp.zeros(128)
    total_sent = jnp.zeros(128)
    for _ in range(steps):
        x = jnp.asarray(rng.normal(size=128), jnp.float32)
        q, s, dec, ef_new = codec.encode_commit(x, ef)
        # per-step bitwise identity: dec + ef' == x + ef
        np.testing.assert_array_equal(
            np.asarray(dec + ef_new), np.asarray(x + ef))
        ef = ef_new
        total_true = total_true + x
        total_sent = total_sent + dec
    np.testing.assert_allclose(
        np.asarray(total_sent + ef), np.asarray(total_true), atol=1e-4
    )


@SET
@given(
    tiles=st.integers(1, 3),
    k=st.integers(1, 8),
    seed=st.integers(0, 1000),
)
def test_topk_mask_keeps_largest(tiles, k, seed):
    """Per-tile top-k mask keeps at least k lanes per tile, every kept lane
    is >= every dropped lane in magnitude, and kept lanes pass through
    unchanged."""
    P = tiles * 128
    x = jnp.asarray(np.random.default_rng(seed).normal(size=P), jnp.float32)
    m = topk_mask(x, k)
    xt = np.asarray(x).reshape(tiles, 128)
    mt = np.asarray(m).reshape(tiles, 128)
    for t in range(tiles):
        kept = np.abs(xt[t])[mt[t] != 0]
        dropped = np.abs(xt[t])[mt[t] == 0]
        assert len(kept) >= k  # ties may keep extras (threshold-based)
        if len(dropped):
            assert kept.min() >= dropped.max()
        np.testing.assert_array_equal(mt[t][mt[t] != 0],
                                      xt[t][mt[t] != 0])


@SET
@given(
    n_leaves=st.integers(1, 5),
    mesh_axis_size=st.sampled_from([1, 2, 4, 8]),
    stacked_n=st.integers(1, 4),
    seed=st.integers(0, 10_000),
)
def test_flat_spec_roundtrip_mixed_dtypes(n_leaves, mesh_axis_size,
                                          stacked_n, seed):
    """FlatSpec ravel/unravel is an exact round-trip for ANY mixed-dtype
    tree and shard-aligned padding: per-leaf target dtypes are restored
    (the cast path the flat forward relies on), values survive the f32
    staging exactly (bf16 and small ints embed losslessly in f32), pad
    lanes are zero, and P splits into mesh_axis_size equal lane-aligned
    shards whose segment tables tile every leaf exactly once."""
    from repro.core.flatten import make_flat_spec
    rng = np.random.default_rng(seed)
    dtypes = [jnp.float32, jnp.bfloat16, jnp.int32]
    tree = {}
    for i in range(n_leaves):
        shape = tuple(int(d) for d in rng.integers(1, 6, size=rng.integers(1, 4)))
        dt = dtypes[int(rng.integers(len(dtypes)))]
        if dt == jnp.int32:
            leaf = jnp.asarray(rng.integers(-1000, 1000, size=shape), dt)
        else:
            # bf16 values are exactly f32-representable by construction
            leaf = jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dt)
        tree[f"leaf{i}"] = leaf
    spec = make_flat_spec(tree, mesh_axis_size=mesh_axis_size)
    flat = spec.ravel(tree)
    assert flat.shape == (spec.padded_size,) and flat.dtype == jnp.float32
    assert spec.padded_size % (mesh_axis_size * 128) == 0
    assert not np.any(np.asarray(flat[spec.size:]))  # pads are zero
    back = spec.unravel(flat)
    raw = spec.unravel(flat, cast=False)
    for k, leaf in tree.items():
        assert back[k].dtype == leaf.dtype
        assert raw[k].dtype == jnp.float32   # cast=False keeps slab dtype
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(leaf, np.float32))
    # stacked variant round-trips too
    stree = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (stacked_n,) + x.shape), tree)
    sback = spec.unravel_stacked(spec.ravel_stacked(stree))
    for k in tree:
        assert sback[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(sback[k], np.float32),
                                      np.asarray(stree[k], np.float32))
    # the shard segment tables tile every leaf exactly once
    covered = {i: 0 for i in range(len(spec.sizes))}
    for s in range(mesh_axis_size):
        lo, hi = spec.shard_ranges()[s]
        assert lo % 128 == 0 and (hi - lo) == spec.shard_size
        for leaf_i, a, b in spec.shard_segments(s):
            assert 0 <= a < b <= spec.sizes[leaf_i]
            covered[leaf_i] += b - a
    leaf_order = sorted(covered)
    assert [covered[i] for i in leaf_order] == list(spec.sizes)


@settings(max_examples=5, deadline=None)
@given(
    n_leaves=st.integers(1, 4),
    stacked_n=st.integers(1, 3),
    seed=st.integers(0, 10_000),
)
def test_tp_exchange_roundtrip_random_layouts(n_leaves, stacked_n, seed):
    """TP-native exchange == replicated oracle for ANY tree and ANY per-leaf
    TP layout on a (2, 4) mesh: ``unravel_sharded`` restores every leaf
    bit-for-bit from the P-shards (non-dividing dims silently drop their
    axis — the ``_fit`` convention — so arbitrary shapes are legal), and
    ``ravel_stacked_sharded`` rebuilds the exact ``[n, P]`` slab.  Few
    examples — each draws two shard_map compiles — but fully random
    geometry."""
    import conftest
    if jax.device_count() < conftest.NDEV:
        pytest.skip(f"needs {conftest.NDEV} devices")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.flatten import make_flat_spec

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(seed)
    dtypes = [jnp.float32, jnp.bfloat16]
    tree, shardings = {}, {}
    axis_menu = [(), ("data",), ("model",), ("data", "model"), ("model", "data")]
    for i in range(n_leaves):
        shape = tuple(int(d) for d in rng.integers(1, 9,
                                                   size=rng.integers(1, 4)))
        dt = dtypes[int(rng.integers(len(dtypes)))]
        leaf = jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dt)
        tree[f"leaf{i}"] = leaf
        # one random axis group on one random dim (or fully replicated)
        entries = [None] * len(shape)
        ax = axis_menu[int(rng.integers(len(axis_menu)))]
        if ax:
            entries[int(rng.integers(len(shape)))] = ax
        shardings[f"leaf{i}"] = NamedSharding(mesh, P(*entries))
    spec = make_flat_spec(tree, mesh_axis_size=8)
    plan = spec.tp_plan(mesh, shardings, axes=("data", "model"))

    back = jax.jit(lambda f: spec.unravel_sharded(f, mesh, plan=plan)
                   )(spec.ravel(tree))
    for k, leaf in tree.items():
        assert back[k].dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(leaf, np.float32))

    stree = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (stacked_n,) + x.shape), tree)
    want = spec.ravel_stacked(stree)   # eager oracle before any placement
    got = jax.jit(lambda t: spec.ravel_stacked_sharded(t, mesh, plan=plan)
                  )(stree)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@SET
@given(
    n=st.integers(2, 5),
    seed=st.integers(0, 500),
)
def test_dude_round_masks_arbitrary(n, seed):
    """dude_round with ARBITRARY mask patterns keeps g_bar == mean of stored
    buffers (the incremental identity at round granularity)."""
    rng = np.random.default_rng(seed)
    cfg = DuDeConfig(n_workers=n)
    like = {"w": jnp.zeros(4)}
    stt = dude_init(like, cfg)
    stored = np.zeros((n, 4))
    latched = np.zeros((n, 4))
    for _ in range(15):
        fresh = rng.normal(size=(n, 4)).astype(np.float32)
        start = rng.random(n) < 0.5
        commit = rng.random(n) < 0.5
        stt, gbar = dude_round(
            stt, {"w": jnp.asarray(fresh)}, jnp.asarray(start),
            jnp.asarray(commit), cfg,
        )
        stored[commit] = latched[commit]
        latched[start] = fresh[start]
        np.testing.assert_allclose(
            np.asarray(gbar["w"]), stored.mean(axis=0), atol=1e-4
        )


@SET
@given(seed=st.integers(0, 300))
def test_compressed_engine_preserves_invariant(seed):
    """Compressed-slab DuDe engine: g_bar must track the mean of the DECODED
    stored rows at every commit — the incremental invariant survives
    quantization because the server folds decoded-new minus decoded-old."""
    from repro.core.engine import DuDeEngine
    rng = np.random.default_rng(seed)
    n = 3
    eng = DuDeEngine.for_tree({"w": jnp.zeros(130)}, n_workers=n,
                              commit_format="int8_ef")
    stt = eng.init()
    codec = eng.codec
    for t in range(12):
        i = int(rng.integers(n))
        g = eng.spec.ravel(
            {"w": jnp.asarray(rng.normal(size=130), jnp.float32)})
        stt, gbar = eng.commit(stt, jnp.int32(i), g)
        decoded = codec.decode(stt.g_workers, stt.gw_scale)
        mean_buf = np.asarray(decoded).mean(axis=0)
        np.testing.assert_allclose(np.asarray(gbar), mean_buf, atol=1e-4)


def test_compressed_engine_converges_quadratic():
    """int8+EF compressed commits still reach the true optimum of a
    heterogeneous quadratic (EF telescopes); the wire payload is ~3.9x
    smaller than f32 commits."""
    from repro.core.engine import DuDeEngine
    rng = np.random.default_rng(0)
    n, P = 4, 128
    A = [np.diag(rng.uniform(0.5, 2.0, P)) for _ in range(n)]
    b = [rng.normal(size=P) * 3 for _ in range(n)]
    wstar = np.linalg.solve(sum(A) / n, sum(b) / n)
    eng = DuDeEngine.for_tree(jnp.zeros(P), n_workers=n,
                              commit_format="int8_ef")
    stt = eng.init()
    w = jnp.zeros(P)
    commit = jax.jit(eng.commit)
    for t in range(600):
        i = t % n
        g = jnp.asarray(A[i] @ np.asarray(w) - b[i], jnp.float32)
        stt, gbar = commit(stt, jnp.int32(i), g)
        w = w - 0.05 * gbar[:P]
    assert np.linalg.norm(np.asarray(w) - wstar) < 0.05
    # the headline byte accounting: >= 3x reduction on wire and in the slab
    codec = eng.codec
    assert codec.commit_wire_bytes(eng.spec.padded_size) * 3 \
        <= 4 * eng.spec.padded_size
    assert codec.slab_bytes(n, eng.spec.padded_size) * 3 \
        <= 4 * n * eng.spec.padded_size
