"""ops/: padding, dedup, lookup, combine kernels."""

import pytest
import jax.numpy as jnp
import numpy as np

from gamesmanmpi_tpu.core.bitops import SENTINEL64 as SENTINEL
from gamesmanmpi_tpu.core.values import WIN, LOSE, TIE, UNDECIDED

# Smoke tier: fast, compile-light, single-process-safe (see pyproject).
pytestmark = pytest.mark.smoke
from gamesmanmpi_tpu.ops import (
    bucket_size,
    pad_to_bucket,
    sort_unique,
    lookup_sorted,
    lookup_window,
    combine_children,
)


def test_bucket_size():
    assert bucket_size(0) == 256
    assert bucket_size(256) == 256
    assert bucket_size(257) == 512
    assert bucket_size(1000) == 1024


def test_pad_to_bucket():
    out = pad_to_bucket(np.array([5, 3], dtype=np.uint64))
    assert out.shape == (256,)
    assert out[0] == 5 and out[1] == 3
    assert (out[2:] == SENTINEL).all()


def test_sort_unique():
    x = np.array([7, 3, 7, SENTINEL, 3, 1, SENTINEL], dtype=np.uint64)
    s, count = sort_unique(jnp.asarray(x))
    assert int(count) == 3
    assert list(np.asarray(s[:3])) == [1, 3, 7]
    assert (np.asarray(s[3:]) == SENTINEL).all()


def _table(states, values, rems):
    states = np.asarray(states, np.uint64)
    order = np.argsort(states)
    return (
        jnp.asarray(states[order]),
        jnp.asarray(np.asarray(values, np.uint8)[order]),
        jnp.asarray(np.asarray(rems, np.int32)[order]),
    )


def test_lookup_sorted_hits_and_misses():
    ts, tv, tr = _table([10, 20, 30], [WIN, LOSE, TIE], [1, 2, 3])
    keys = jnp.asarray(np.array([20, 5, 30, 99, SENTINEL], dtype=np.uint64))
    v, r, hit = lookup_sorted(keys, ts, tv, tr)
    assert list(np.asarray(hit)) == [True, False, True, False, False]
    assert list(np.asarray(v)) == [LOSE, UNDECIDED, TIE, UNDECIDED, UNDECIDED]
    assert list(np.asarray(r)) == [2, 0, 3, 0, 0]


def test_lookup_window_multi_level():
    w1 = _table([10, 20], [WIN, LOSE], [1, 2])
    w2 = _table([30, 40], [TIE, WIN], [3, 4])
    keys = jnp.asarray(np.array([40, 10, 77], dtype=np.uint64))
    v, r, hit = lookup_window(keys, (w1, w2))
    assert list(np.asarray(hit)) == [True, True, False]
    assert list(np.asarray(v)) == [WIN, WIN, UNDECIDED]
    assert list(np.asarray(r)) == [4, 1, 0]


def test_combine_children_rules():
    # Rows: (child values, child rems, mask) -> expected (value, rem).
    cv = jnp.asarray(
        np.array(
            [
                [LOSE, WIN, LOSE],  # WIN: 1 + min(LOSE rems 5, 2) = 3
                [WIN, TIE, WIN],  # TIE: 1 + max(TIE rems) = 8
                [WIN, WIN, WIN],  # LOSE: 1 + max(all rems) = 10
                [LOSE, LOSE, LOSE],  # masked lanes ignored
            ],
            dtype=np.uint8,
        )
    )
    cr = jnp.asarray(np.array([[5, 9, 2], [1, 7, 3], [4, 9, 6], [5, 1, 9]], np.int32))
    mask = jnp.asarray(
        np.array(
            [
                [True, True, True],
                [True, True, True],
                [True, True, True],
                [True, False, False],
            ]
        )
    )
    v, r = combine_children(cv, cr, mask)
    assert list(np.asarray(v)) == [WIN, TIE, LOSE, WIN]
    assert list(np.asarray(r)) == [3, 8, 10, 6]


def test_combine_children_no_children():
    cv = jnp.zeros((1, 3), jnp.uint8)
    cr = jnp.zeros((1, 3), jnp.int32)
    mask = jnp.zeros((1, 3), bool)
    v, r = combine_children(cv, cr, mask)
    assert int(v[0]) == LOSE and int(r[0]) == 0


def test_route_by_owner_roundtrip():
    """The owner-bucketing primitive: every non-sentinel element lands in
    exactly its owner's row, counts are exact, and (s_owner, pos, order)
    invert the permutation — the contract the backward reply routing
    depends on."""
    import jax

    from gamesmanmpi_tpu.core.hashing import owner_shard_np
    from gamesmanmpi_tpu.parallel.sharded import _route_by_owner

    rng = np.random.default_rng(7)
    S, cap = 4, 64
    flat = rng.integers(0, 1 << 40, size=100, dtype=np.uint64)
    flat[::7] = SENTINEL  # padding lanes
    send, counts, s_owner, pos, order = jax.jit(
        lambda x: _route_by_owner(x, S, cap, SENTINEL),
        static_argnums=(),
    )(jnp.asarray(flat))
    send = np.asarray(send)
    counts = np.asarray(counts)
    owners = owner_shard_np(flat, S)
    real = flat != SENTINEL
    # counts per destination are exact
    for s in range(S):
        assert counts[s] == int((owners[real] == s).sum())
        got = send[s][send[s] != SENTINEL]
        want = np.sort(flat[real & (owners == s)])
        assert sorted(got.tolist()) == sorted(want.tolist())
    # the inverse permutation reconstructs the original layout
    s_owner = np.asarray(s_owner)
    pos = np.asarray(pos)
    order = np.asarray(order)
    recon = np.empty_like(flat)
    gathered = np.where(
        s_owner < S, send[np.clip(s_owner, 0, S - 1), pos], SENTINEL
    )
    recon[order] = gathered
    assert (recon == flat).all()


def test_route_by_owner_overflow_drops_and_counts():
    """Overflowed elements drop from the send buffer but counts still report
    the true demand (what the host retry loop keys on)."""
    import jax

    from gamesmanmpi_tpu.parallel.sharded import _route_by_owner

    flat = jnp.asarray(np.arange(100, dtype=np.uint64))
    send, counts, _, _, _ = jax.jit(
        lambda x: _route_by_owner(x, 2, 8, SENTINEL)
    )(flat)
    counts = np.asarray(counts)
    assert counts.sum() == 100  # true demand, not the truncated buffer
    assert counts.max() > 8  # the overflow the host must detect
    send = np.asarray(send)
    assert (send != SENTINEL).sum() == 16  # buffer capped at S*cap


def test_expand_provenance_contract():
    """expand_provenance must agree with expand_core on (uniq, count), its
    prim with game.primitive, and perm/nreal must list every child slot
    once in the dedup sort's order: the nreal real children first, in
    non-decreasing state order, the run-first bit exactly where a new
    state starts, so that the run index (cumsum of those bits, less one)
    is each child's index in the uniq prefix — the invariant the backward
    pass's sorted-order fetch rests on."""
    import jax

    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.solve.engine import (
        canonical_children,
        expand_core,
        expand_provenance,
        undecided_mask,
    )

    for spec in ("tictactoe", "connect4:w=4,h=4", "chomp:w=3,h=3"):
        game = get_game(spec)
        # A frontier with real states, duplicates of children guaranteed
        # (siblings share children via transpositions), and sentinel pads.
        init = game.initial_state()
        kids, _ = jax.jit(lambda s: game.expand(s))(
            jnp.asarray([init], dtype=game.state_dtype)
        )
        pool = np.unique(np.asarray(kids).reshape(-1))
        pool = pool[pool != game.sentinel]
        states = np.full(64, game.sentinel, dtype=game.state_dtype)
        states[: pool.shape[0]] = pool
        states_j = jnp.asarray(states)

        uniq_c, count_c = jax.jit(lambda s: expand_core(game, s))(states_j)
        uniq_p, count_p, perm, nreal, prim = jax.jit(
            lambda s: expand_provenance(game, s)
        )(states_j)
        assert int(count_c) == int(count_p)
        assert (np.asarray(uniq_c) == np.asarray(uniq_p)).all()
        assert (
            np.asarray(prim) == np.asarray(jax.jit(game.primitive)(states_j))
        ).all()

        children, mask = jax.jit(
            lambda s: canonical_children(game, s, undecided_mask(game, s))
        )(states_j)
        flat = np.asarray(children).reshape(-1)
        m = np.asarray(mask).reshape(-1)
        p = np.asarray(perm)
        first = p < 0
        origin = p & 0x7FFFFFFF
        uq = np.asarray(uniq_p)
        n, real = int(count_p), int(nreal)
        assert sorted(origin.tolist()) == list(range(flat.shape[0]))
        assert real == int((flat != game.sentinel).sum())
        kids_sorted = flat[origin]
        assert (kids_sorted[real:] == game.sentinel).all()
        assert not first[real:].any()
        assert m[origin[:real]].all()
        assert (np.diff(kids_sorted[:real]) > 0).tolist() == \
            first[1:real].tolist()
        assert real == 0 or first[0]
        uid = np.cumsum(first) - 1
        assert int(first.sum()) == n
        assert (uq[uid[:real]] == kids_sorted[:real]).all()


def _window_uid(pattern, n, seed):
    """A run index over n slots: steps of 0 or 1 from 0."""
    rng = np.random.default_rng(seed)
    if pattern == "all_steps":
        steps = np.ones(n, np.int32)
    elif pattern == "random_steps":
        steps = rng.integers(0, 2, size=n)
    else:  # long runs of equal children, then dense steps
        steps = (rng.random(n) < 0.05).astype(np.int32)
        steps[n // 2:] = 1
    steps[0] = 0
    return np.cumsum(steps).astype(np.int32)


@pytest.mark.parametrize("pattern", ["all_steps", "random_steps",
                                     "runs_then_steps"])
def test_window_gather_serves_run_index_from_views(pattern):
    """The run index steps by 0 or 1 per slot, so a block of 1024 lanes
    spans at most 1024 entries of a 4-byte table: the window kernel
    (interpreted) at SORTED_FETCH_WINDOW serves every lane from its views
    and gives table[uid] exactly."""
    from gamesmanmpi_tpu.ops.pallas_gather import monotone_window_gather
    from gamesmanmpi_tpu.ops.provenance import SORTED_FETCH_WINDOW

    assert SORTED_FETCH_WINDOW == 2048
    n = 9 * 1024 + 77  # a partial last block
    uid = _window_uid(pattern, n, seed=len(pattern))
    rng = np.random.default_rng(1)
    table = rng.integers(0, 1 << 32, size=int(uid[-1]) + 300,
                         dtype=np.uint32)
    out, missed = monotone_window_gather(
        jnp.asarray(table), jnp.asarray(uid), window=SORTED_FETCH_WINDOW,
        interpret=True)
    assert int(missed) == 0
    np.testing.assert_array_equal(np.asarray(out), table[uid])


def _fetch_case(case, dtype):
    """[C*M] child states for the sorted-fetch parity test."""
    rng = np.random.default_rng(len(case))
    sentinel = np.iinfo(dtype).max
    n = 1024 * 4
    if case == "all_sentinel":
        return np.full(n, sentinel, dtype)
    if case == "single_unique":
        x = np.full(n, 12345, dtype)
        x[rng.random(n) < 0.2] = sentinel
        return x
    x = rng.integers(0, n // 3, size=n).astype(dtype)
    if case == "sentinel_tail":
        x[n // 3:] = sentinel
    else:
        x[rng.random(n) < 0.15] = sentinel
    return x


FETCH_CASES = [(c, d) for c in ("runs", "sentinel_tail")
                for d in (np.uint32, np.uint64)
               ] + [("all_sentinel", np.uint32), ("single_unique", np.uint64),
                    ("padded", np.uint32)]


@pytest.mark.parametrize(
    "case,dtype", FETCH_CASES,
    ids=[f"{c}-{np.dtype(d).name}" for c, d in FETCH_CASES])
def test_sorted_fetch_matches_parent_order_gather(case, dtype):
    """The classic backward's fetch in the dedup sort's order plus its
    un-permute sort gives each child slot the cell that a gather in parent
    slot order through dedup_provenance's uidx gives (cell 0 where there is
    no child), moves major; through XLA's gather and through the Pallas
    window kernel (interpreted), which misses no lane. "padded": a level
    padded to a larger common capacity, as the backward pads it."""
    import jax

    from gamesmanmpi_tpu.core.codec import pack_cells_np
    from gamesmanmpi_tpu.ops import dedup_provenance, dedup_provenance_sorted
    from gamesmanmpi_tpu.ops.provenance import PERM_PAD, sorted_child_cells

    M = 4
    x = _fetch_case("runs" if case == "padded" else case, dtype)
    _, count, uidx = jax.jit(dedup_provenance)(jnp.asarray(x))
    uniq, count_s, perm, nreal = jax.jit(dedup_provenance_sorted)(
        jnp.asarray(x))
    assert int(count_s) == int(count)
    uidx = np.asarray(uidx)
    if case == "padded":
        slots = 2 * x.shape[0]
        perm = jnp.concatenate(
            [perm, jnp.full(slots - perm.shape[0], PERM_PAD, jnp.int32)])
        uidx = np.concatenate([uidx, np.full(slots - uidx.shape[0], -1,
                                             np.int32)])
    W = max(int(count), 1) + 5
    rng = np.random.default_rng(2)
    wvals = rng.integers(1, 4, size=W).astype(np.uint8)
    wrem = rng.integers(0, 40, size=W).astype(np.int32)
    cells = pack_cells_np(wvals, wrem)
    want = np.where(uidx >= 0, cells[np.clip(uidx, 0, W - 1)], 0)
    want = want.reshape(-1, M).T  # moves major
    for method in ("plain", "pallas"):
        got, missed = jax.jit(
            lambda p, r, v, w: sorted_child_cells(p, r, v, w, M, method)
        )(perm, nreal, jnp.asarray(wvals), jnp.asarray(wrem))
        assert int(missed) == 0, method
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=method)


# ------------------------------------------- platform lowerings vs NumPy
#
# The dedup and lookup kernels carry two lowerings each, picked from the
# platform: compaction "resort" (accelerators) or "scatter" (CPU), search
# "sort" (accelerators) or "scan" (CPU). The CPU suite otherwise runs only
# the CPU side, so each case below passes both explicitly and checks them
# against NumPy, over lengths that are not powers of two and both state
# widths, with sentinel-masked lanes mixed in.

GRID_N = [1, 2, 7, 128, 1000, 4096, 10_000]
GRID_DTYPES = [np.uint32, np.uint64]


def _masked_states(n, dtype, seed):
    """[n] states with duplicates and ~15% sentinel lanes."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, max(n // 2, 1), size=n).astype(dtype)
    x[rng.random(n) < 0.15] = np.iinfo(dtype).max
    return x


@pytest.mark.parametrize("n", GRID_N)
@pytest.mark.parametrize("dtype", GRID_DTYPES)
def test_sort_unique_matches_numpy(n, dtype):
    import jax

    x = _masked_states(n, dtype, seed=n)
    sentinel = np.iinfo(dtype).max
    want = np.unique(x[x != sentinel])
    for compact in ("scatter", "resort"):
        s, count = jax.jit(lambda v: sort_unique(v, compact))(jnp.asarray(x))
        s = np.asarray(s)
        assert int(count) == want.shape[0], compact
        np.testing.assert_array_equal(s[: want.shape[0]], want,
                                      err_msg=compact)
        assert (s[want.shape[0]:] == sentinel).all(), compact


@pytest.mark.parametrize("n", GRID_N)
@pytest.mark.parametrize("dtype", GRID_DTYPES)
def test_dedup_provenance_matches_numpy(n, dtype):
    import jax

    from gamesmanmpi_tpu.ops import dedup_provenance

    x = _masked_states(n, dtype, seed=n + 1)
    sentinel = np.iinfo(dtype).max
    real = x != sentinel
    want, inverse = np.unique(x[real], return_inverse=True)
    want_uidx = np.full(n, -1, np.int32)
    want_uidx[real] = inverse
    for compact in ("scatter", "resort"):
        uniq, count, uidx = jax.jit(
            lambda v: dedup_provenance(v, compact)
        )(jnp.asarray(x))
        uniq = np.asarray(uniq)
        assert int(count) == want.shape[0], compact
        np.testing.assert_array_equal(uniq[: want.shape[0]], want,
                                      err_msg=compact)
        assert (uniq[want.shape[0]:] == sentinel).all(), compact
        np.testing.assert_array_equal(np.asarray(uidx), want_uidx,
                                      err_msg=compact)


@pytest.mark.parametrize("n", GRID_N)
@pytest.mark.parametrize("dtype", GRID_DTYPES)
def test_lookup_sorted_matches_numpy(n, dtype):
    import jax

    rng = np.random.default_rng(n + 2)
    sentinel = np.iinfo(dtype).max
    # A solved level: sorted distinct states (even numbers), sentinel tail.
    real = np.unique(rng.integers(0, 4 * n, size=n).astype(dtype) * 2)
    m = real.shape[0]
    states = np.full(n, sentinel, dtype)
    states[:m] = real
    values = np.full(n, UNDECIDED, np.uint8)
    values[:m] = rng.choice([WIN, LOSE, TIE], size=m)
    rems = np.zeros(n, np.int32)
    rems[:m] = rng.integers(0, 200, size=m)
    # Queries: hits, misses (odd numbers are never stored) and sentinels.
    keys = np.where(rng.random(n) < 0.5, real[rng.integers(0, m, size=n)],
                    rng.integers(0, 8 * n, size=n).astype(dtype) | 1)
    keys = keys.astype(dtype)
    keys[rng.random(n) < 0.15] = sentinel
    idx = np.clip(np.searchsorted(real, keys), 0, m - 1)
    hit = (real[idx] == keys) & (keys != sentinel)
    want_v = np.where(hit, values[idx], UNDECIDED)
    want_r = np.where(hit, rems[idx], 0)
    table = tuple(jnp.asarray(a) for a in (states, values, rems))
    for method in ("scan", "sort"):
        v, r, h = jax.jit(
            lambda k, *t: lookup_sorted(k, *t, method=method)
        )(jnp.asarray(keys), *table)
        np.testing.assert_array_equal(np.asarray(h), hit, err_msg=method)
        np.testing.assert_array_equal(np.asarray(v), want_v, err_msg=method)
        np.testing.assert_array_equal(np.asarray(r), want_r, err_msg=method)
