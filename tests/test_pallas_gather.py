"""Monotone-window gather (ops/pallas_gather.py), interpret mode.

These tests pin the kernel's SEMANTICS via the Pallas interpreter so the
on-chip run (tools/pallas_chip_check.py) only has to answer "does Mosaic
accept it and is it fast", not "is it correct". The round-4 chip session
compiled a trivial Pallas kernel; this kernel's first compile attempt
exposed a trace-time int64 recursion (fixed — see
ops/pallas_gather._dyn_gather). The installed Mosaic still refuses the
kernel ("Multiple source vregs along gather dimension"), pinned as a
strict xfail in tests/test_tpu_compile.py.
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
    table = rng.integers(0, 1 << 30, size=m, dtype=np.uint32)
    if span is None:
        idx = np.sort(rng.integers(0, m, size=n)).astype(np.int32)
    else:
        # Bounded local span: index i drifts forward like the dense child
        # gathers do (expansion ratio <= 2).
        steps = rng.integers(0, span, size=n)
        idx = np.minimum(np.cumsum(steps), m - 1).astype(np.int32)
    return table, idx


def test_matches_plain_gather_when_spans_fit():
    table, idx = _case(1 << 16, 5000, 0, span=3)
    out, nmiss = monotone_window_gather(table, idx, block=256, window=2048,
                                        interpret=True)
    assert int(nmiss) == 0
    np.testing.assert_array_equal(np.asarray(out), table[idx])


def test_int64_indices_match_int32():
    """int64 idx (6x6+ flat spaces) must produce bit-identical output:
    the wrapper reduces both dtypes to the same block-local int32
    offsets before Mosaic (r5 — VERDICT r4 #3)."""
    table, idx = _case(1 << 16, 5000, 3, span=3)
    out32, nm32 = monotone_window_gather(table, idx, block=256,
                                         window=2048, interpret=True)
    out64, nm64 = monotone_window_gather(table, idx.astype(np.int64),
                                         block=256, window=2048,
                                         interpret=True)
    assert int(nm32) == int(nm64) == 0
    np.testing.assert_array_equal(np.asarray(out32), np.asarray(out64))
    np.testing.assert_array_equal(np.asarray(out64), table[idx])


def test_int64_wide_jumps_miss_flagged():
    table, idx = _case(1 << 18, 4096, 4)
    out, nmiss = monotone_window_gather(table, idx.astype(np.int64),
                                        block=256, window=1024,
                                        interpret=True)
    assert int(nmiss) > 0
    ok = _reference_ok_mask(table, idx, block=256, window=1024)
    np.testing.assert_array_equal(np.asarray(out)[ok], table[idx[ok]])
    assert int(nmiss) == int((~ok).sum())


def test_wide_jumps_are_miss_flagged_not_wrong():
    # Random global indices jump across windows: misses must be counted,
    # and every non-missed element must still be correct.
    table, idx = _case(1 << 18, 4096, 1)
    out, nmiss = monotone_window_gather(table, idx, block=256, window=1024,
                                        interpret=True)
    assert int(nmiss) > 0  # adversarial case: spans exceed the window
    ok = _reference_ok_mask(table, idx, block=256, window=1024)
    np.testing.assert_array_equal(np.asarray(out)[ok], table[idx[ok]])
    assert int(nmiss) == int((~ok).sum())


def test_u8_table_gathers_as_i32_exactly():
    # The dense engine's tables are u8 cells; the kernel gathers them as
    # i32 in VMEM (Mosaic's dynamic_gather targets 32-bit lanes) and must
    # cast back exactly.
    rng = np.random.default_rng(7)
    table = rng.integers(0, 256, size=1 << 16, dtype=np.uint8)
    steps = rng.integers(0, 3, size=5000)
    idx = np.minimum(np.cumsum(steps), table.shape[0] - 1).astype(np.int32)
    out, nmiss = monotone_window_gather(table, idx, block=256, window=2048,
                                        interpret=True)
    assert int(nmiss) == 0
    assert np.asarray(out).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(out), table[idx])


def _reference_ok_mask(table, idx, block, window):
    """The kernel's hit predicate, recomputed independently: element i
    hits iff its offset from its block's clamped window base lies in
    [0, 2*window)."""
    n = idx.shape[0]
    ok = np.zeros(n, bool)
    nwin = max(-(-table.shape[0] // window), 2)
    for b in range(-(-n // block)):
        lo, hi = b * block, min((b + 1) * block, n)
        base = min(max(idx[lo] // window, 0), nwin - 2) * window
        off = idx[lo:hi] - base
        ok[lo:hi] = (off >= 0) & (off < 2 * window)
    return ok


@settings(max_examples=25, deadline=None)
@given(
    logm=st.integers(10, 16),
    n=st.integers(1, 4000),
    block=st.sampled_from([128, 256, 512]),
    window=st.sampled_from([1024, 2048, 4096]),
    local=st.booleans(),
    seed=st.integers(0, 2**31),
)
def test_property_hits_exact_misses_flagged(logm, n, block, window, local,
                                            seed):
    # For ANY table size, length, block/window config and ANY
    # non-decreasing index vector: (a) every non-missed element equals
    # table[idx]; (b) nmiss == 0 exactly when no real element misses;
    # (c) when misses exist, nmiss covers at least the real ones (tail
    # padding replicas may inflate it, per the contract).
    table, idx = _case(1 << logm, n, seed, span=3 if local else None)
    out, nmiss = monotone_window_gather(table, idx, block=block,
                                        window=window, interpret=True)
    ok = _reference_ok_mask(table, idx, block, window)
    np.testing.assert_array_equal(np.asarray(out)[ok], table[idx[ok]])
    real_misses = int((~ok).sum())
    if real_misses == 0:
        assert int(nmiss) == 0
    else:
        assert int(nmiss) >= real_misses


@pytest.mark.parametrize("n", [1, 255, 256, 257, 5000])
def test_ragged_lengths(n):
    table, idx = _case(1 << 14, n, n, span=2)
    out, nmiss = monotone_window_gather(table, idx, block=256, window=2048,
                                        interpret=True)
    assert int(nmiss) == 0
    np.testing.assert_array_equal(np.asarray(out), table[idx])
