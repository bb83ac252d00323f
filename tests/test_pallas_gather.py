"""Monotone-window gather (ops/pallas_gather.py), interpret mode.

These tests pin the kernel's SEMANTICS via the Pallas interpreter, so a
chip run only has to answer "is it fast": the output equals ``table[idx]``
on every lane (lanes outside both views through the fix-up), and the miss
count equals the lanes outside both views, recomputed independently
(``_reference_ok_mask``). tests/test_tpu_compile.py compiles the same
kernel for a described v5e.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    # No hypothesis on this image (and no pip install allowed): the
    # properties run through the deterministic mini shim instead of
    # failing tier-1 collection. See tests/_mini_hypothesis.py.
    from _mini_hypothesis import given, settings, st

from gamesmanmpi_tpu.ops.pallas_gather import monotone_window_gather

# Smoke tier: fast, compile-light, single-process-safe (see pyproject).
pytestmark = pytest.mark.smoke


def _case(m, n, seed, span=None):
    rng = np.random.default_rng(seed)
    table = rng.integers(0, 1 << 32, size=m, dtype=np.uint32)
    if span is None:
        idx = np.sort(rng.integers(0, m, size=n)).astype(np.int32)
    else:
        # Bounded local span: index i drifts forward like the dense child
        # gathers do (expansion ratio <= 2).
        steps = rng.integers(0, span, size=n)
        idx = np.minimum(np.cumsum(steps), m - 1).astype(np.int32)
    return table, idx


def _gather(table, idx, **kw):
    out, nmiss = monotone_window_gather(table, idx, interpret=True, **kw)
    return np.asarray(out), int(nmiss)


def test_matches_plain_gather_when_spans_fit():
    table, idx = _case(1 << 16, 5000, 0, span=3)
    out, nmiss = _gather(table, idx, window=2048)
    assert nmiss == 0
    np.testing.assert_array_equal(out, table[idx])


def test_int64_indices_match_int32():
    """int64 idx (6x6+ flat spaces) must produce bit-identical output:
    the wrapper reduces them to block-local int32 offsets before Mosaic."""
    table, idx = _case(1 << 16, 5000, 3, span=3)
    out32, nm32 = _gather(table, idx, window=2048)
    out64, nm64 = _gather(table, idx.astype(np.int64), window=2048)
    assert nm32 == nm64 == 0
    np.testing.assert_array_equal(out32, out64)
    np.testing.assert_array_equal(out64, table[idx])


def test_int64_wide_jumps_miss_flagged():
    table, idx = _case(1 << 18, 4096, 4)
    out, nmiss = _gather(table, idx.astype(np.int64), window=1024)
    ok = _reference_ok_mask(table, idx, block=1024, window=1024)
    assert nmiss == int((~ok).sum()) > 0
    np.testing.assert_array_equal(out, table[idx])


def test_wide_jumps_are_miss_flagged_not_wrong():
    # Random global indices jump across views: the misses are counted, and
    # the fix-up still gives every lane its exact value.
    table, idx = _case(1 << 18, 4096, 1)
    out, nmiss = _gather(table, idx, window=1024)
    ok = _reference_ok_mask(table, idx, block=1024, window=1024)
    assert nmiss == int((~ok).sum()) > 0
    np.testing.assert_array_equal(out, table[idx])


def test_u8_table_gathers_as_i32_exactly():
    # The dense engine's tables are u8 cells; the kernel selects them from
    # packed i32 words (Mosaic's dynamic_gather targets 32-bit lanes) and
    # must narrow back exactly.
    rng = np.random.default_rng(7)
    table = rng.integers(0, 256, size=1 << 16, dtype=np.uint8)
    steps = rng.integers(0, 3, size=5000)
    idx = np.minimum(np.cumsum(steps), table.shape[0] - 1).astype(np.int32)
    out, nmiss = _gather(table, idx)
    assert nmiss == 0
    assert out.dtype == np.uint8
    np.testing.assert_array_equal(out, table[idx])


def _reference_ok_mask(table, idx, block, window):
    """The kernel's hit predicate, recomputed independently: a lane hits
    iff it lies in one of its block's two views — A from the tile of the
    block's first index, B up to the end of the tile of its last — each
    `window` elements, clamped inside the padded table."""
    per_word = 4 // table.dtype.itemsize
    row_elems = 128 * per_word
    tile = 8 * row_elems
    rows = max(-(-table.shape[0] // tile) * 8, window // row_elems)
    hi = rows * row_elems - window
    n = idx.shape[0]
    ok = np.zeros(n, bool)
    for b in range(-(-n // block)):
        lanes = idx[b * block:(b + 1) * block].astype(np.int64)
        a = min(lanes[0] // tile * tile, hi)
        v = min(max((lanes[-1] // tile + 1) * tile - window, 0), hi)
        ok[b * block:(b + 1) * block] = (
            ((lanes >= a) & (lanes < a + window))
            | ((lanes >= v) & (lanes < v + window)))
    return ok


def _one_jump(rng):
    """Every block but a few runs locally; each of those has one jump far
    beyond view A, which view B serves."""
    table = rng.integers(0, 256, size=1 << 18, dtype=np.uint8)
    idx = np.minimum(np.cumsum(rng.integers(0, 2, size=8192)), 40_000)
    idx[1500:2048] += 100_000       # inside block 1, to its end
    idx[2048:] += 150_000           # every later block local again
    return table, idx.astype(np.int32), 0


def _wider_than_views(rng):
    """Blocks spanning far more than both views: the fix-up serves their
    middle lanes exactly, and counts them."""
    table = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
    idx = np.sort(rng.integers(0, table.shape[0], size=3000))
    return table, idx.astype(np.int32), None


def _u8_int64(rng):
    table = rng.integers(0, 256, size=1 << 17, dtype=np.uint8)
    idx = np.minimum(np.cumsum(rng.integers(0, 4, size=6000)),
                     table.shape[0] - 1)
    return table, idx.astype(np.int64), 0


def _monotone_fill(rng):
    """The dense step's fill: runs that repeat one slot (invalid rows, pad
    ranks) between strictly increasing child ranks, row after row."""
    table = rng.integers(0, 256, size=1 << 16, dtype=np.uint8)
    parts, base = [], 0
    for _ in range(6):
        run = base + np.cumsum(rng.integers(1, 3, size=900))
        parts += [run, np.full(300, run[-1])]
        base = run[-1] + int(rng.integers(0, 5000))
    return table, np.concatenate(parts).astype(np.int32), 0


@pytest.mark.parametrize("make", [_one_jump, _wider_than_views, _u8_int64,
                                  _monotone_fill],
                         ids=["one_jump", "wider_than_views", "u8_int64",
                              "monotone_fill"])
def test_structured_index_patterns(make):
    table, idx, want_miss = make(np.random.default_rng(11))
    out, nmiss = _gather(table, idx)
    np.testing.assert_array_equal(out, table[idx])
    ok = _reference_ok_mask(table, idx, block=1024, window=8192)
    assert nmiss == int((~ok).sum())
    if want_miss is not None:
        assert nmiss == want_miss
    else:
        assert nmiss > 0


@settings(max_examples=25, deadline=None)
@given(
    logm=st.integers(10, 16),
    n=st.integers(1, 4000),
    block=st.sampled_from([1024, 2048]),
    window=st.sampled_from([1024, 2048, 4096]),
    local=st.booleans(),
    seed=st.integers(0, 2**31),
)
def test_property_hits_exact_misses_flagged(logm, n, block, window, local,
                                            seed):
    # For ANY table size, length, block/window config and ANY
    # non-decreasing index vector: every lane equals table[idx], and the
    # miss count is exactly the lanes outside both views.
    table, idx = _case(1 << logm, n, seed, span=3 if local else None)
    out, nmiss = _gather(table, idx, block=block, window=window)
    np.testing.assert_array_equal(out, table[idx])
    ok = _reference_ok_mask(table, idx, block, window)
    assert nmiss == int((~ok).sum())


@pytest.mark.parametrize("n", [1, 255, 256, 257, 5000, 1023, 1025, 65537])
def test_ragged_lengths(n):
    table, idx = _case(1 << 14, n, n, span=2)
    out, nmiss = _gather(table, idx, window=2048)
    assert nmiss == 0
    np.testing.assert_array_equal(out, table[idx])
