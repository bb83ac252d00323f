"""Fused rank/sort+dedup: the single-stage dedup core of the level megakernel.

ISSUE 14's roofline push starts from a measurement, not a hunch: on the CPU
fallback that produced every committed bench so far, XLA's sort is the solve
(`BENCH_r05.json` operand_gbps 0.069). Microbenchmarks on this host:

    jnp.sort            4M u32   0.324 s     (XLA comparator network)
    np.sort             4M u32   0.023 s     (numpy radix sort, 14x)
    lax.sort (k, i32)   4M pairs 1.452 s     (the provenance pair sort)
    np.unique           4M u32   0.042 s     (sort + dedup + compact, fused)

So the fused dedup has two lowerings, resolved per platform at kernel-BUILD
time exactly like the sort/search/compact knobs (GAMESMAN_FUSED_DEDUP
overrides for A/B):

* ``callback`` (CPU default): one `jax.pure_callback` into numpy's radix
  sort+unique. On the CPU backend the "device" IS the host, so the callback
  is a function call, not a transfer — and it unlocks something static-shape
  XLA cannot express: the megakernel threads the previous level's COUNT into
  the callback, which dedups only the real prefix instead of the padded
  capacity (bucket padding makes those differ by up to 2x). Misuse guard:
  this lowering would be a host round-trip on a real accelerator; the
  platform-auto default only picks it on CPU.
* ``scatterinv`` (accelerator default): the pair-sort trick of
  ops/provenance.dedup_provenance with its second pair sort replaced by a
  permutation-inverting scatter (`ops.mergesort.sort_rank`): the sorted
  origin column IS a permutation, so one O(n) scatter routes each run's
  unique-index back to its origin slot. One pair sort + compaction instead
  of two pair sorts + compaction — measured 1.5x on this host's pair-sort
  costs, and on TPU it removes one full ~log2(n)-pass sort network from the
  forward's HBM traffic.

Both lowerings are byte-parity-tested against sort_unique/dedup_provenance
(tests/test_fused.py); every consumer keys its kernel cache on the resolved
method so a mid-process flag flip can never mix programs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.ops.dedup import compact_sorted, sort_unique
from gamesmanmpi_tpu.ops.mergesort import sort_rank
from gamesmanmpi_tpu.utils.env import env_int, env_str
from gamesmanmpi_tpu.utils.platform import platform_auto_flag


def fused_enabled() -> bool:
    """GAMESMAN_FUSED=1: engines collapse each level's forward path into
    one megakernel dispatch (and, where the gate below allows, the
    backward into one table-resolve dispatch). Default OFF — every fused
    variant lands behind this gate with byte-parity A/B against the
    unfused path (ISSUE 14)."""
    return env_str("GAMESMAN_FUSED", "0") not in ("0", "", "off", "false")


def pipeline_mode() -> str:
    """GAMESMAN_PIPELINE: 'level' (default — each level's host work runs
    before the next dispatch, the historical order) or 'pingpong' (level
    N's host-side downloads/export/checkpoint run AFTER level N-1's kernel
    is dispatched, overlapping them with device execution; the deferred
    seconds are reported as stats.overlap_secs)."""
    v = env_str("GAMESMAN_PIPELINE", "level")
    if v not in ("level", "pingpong"):
        raise ValueError(
            f"GAMESMAN_PIPELINE={v!r}: expected 'level' or 'pingpong'"
        )
    return v


def fused_dedup_method() -> str:
    """Fused-dedup lowering, resolved at builder/cache-key time for the
    executing platform (module docstring has the measurements)."""
    return platform_auto_flag(
        "GAMESMAN_FUSED_DEDUP", accel="scatterinv", cpu="callback",
        choices=("callback", "scatterinv"),
    )


def value_table_bits() -> int:
    """Direct-address value-table gate for the fused backward: games whose
    packed states fit this many bits (and run in uint32) resolve against a
    persistent [2^bits] packed-cell table — one gather per child instead
    of a per-level search — at 4*2^bits bytes of device memory. Default 26
    (256 MB) covers every uint32 board through 5x4; 0 disables."""
    return env_int("GAMESMAN_FUSED_TABLE_BITS", 26)


def use_value_table(game) -> bool:
    """Whether the fused backward may use the direct-address cell table."""
    bits = value_table_bits()
    return (
        bits > 0
        and game.state_bits <= bits
        and np.dtype(game.state_dtype).itemsize == 4
    )


# ------------------------------------------------------------- callback side


def _np_sort_unique(flat, nvalid):
    """Host half of the callback lowering: radix sort+unique over the real
    prefix. Engine contract mirror of ops.dedup.sort_unique: uniques first
    (ascending), sentinel tail, int32 count."""
    flat = np.asarray(flat)
    n = min(max(int(nvalid), 0), flat.shape[0])
    sent = np.iinfo(flat.dtype).max
    u = np.unique(flat[:n])
    k = int(u.shape[0])
    if k and u[-1] == sent:
        k -= 1
    out = np.full(flat.shape[0], sent, dtype=flat.dtype)
    out[:k] = u[:k]
    return out, np.int32(k)


def _np_dedup_provenance(flat, nvalid):
    """Host half with provenance: np.unique's return_inverse IS uidx (the
    index of each input slot within the unique prefix; -1 for sentinel and
    beyond-count slots) — the quantity dedup_provenance reconstructs with a
    second pair sort."""
    flat = np.asarray(flat)
    n = min(max(int(nvalid), 0), flat.shape[0])
    sent = np.iinfo(flat.dtype).max
    u, inv = np.unique(flat[:n], return_inverse=True)
    k = int(u.shape[0])
    if k and u[-1] == sent:
        k -= 1
    out = np.full(flat.shape[0], sent, dtype=flat.dtype)
    out[:k] = u[:k]
    uidx = np.full(flat.shape[0], -1, dtype=np.int32)
    if n:
        # inv == k only for sentinel slots (the one unique past the
        # prefix); everything else indexes the kept uniques directly.
        uidx[:n] = np.where(inv < k, inv, -1).astype(np.int32)
    return out, np.int32(k), uidx


# ---------------------------------------------------------------- public api


def _nvalid_or_full(flat, nvalid):
    if nvalid is None:
        return jnp.int32(flat.shape[0])
    return jnp.minimum(nvalid.astype(jnp.int32), jnp.int32(flat.shape[0]))


@jax.named_scope("dedup")
def fused_sort_unique(flat, nvalid=None, method: str | None = None,
                      merge: bool | None = None, compact: str | None = None):
    """sort_unique with the fused lowering: [N] -> (uniq [N], count).

    nvalid: optional traced count of real leading slots — the callback
    lowering dedups only that prefix (slots past it must already be
    sentinel; the engines guarantee this because children of beyond-count
    parents are sentinel-masked). method/merge/compact: lowerings resolved
    at BUILD time by kernel builders (None = resolve at trace time).
    """
    if method is None:
        method = fused_dedup_method()
    if method == "callback":
        return jax.pure_callback(
            _np_sort_unique,
            (
                jax.ShapeDtypeStruct(flat.shape, flat.dtype),
                jax.ShapeDtypeStruct((), np.int32),
            ),
            flat,
            _nvalid_or_full(flat, nvalid),
        )
    # scatterinv has no non-provenance shortcut — plain dedup already is
    # one sort + compaction; share it so the two paths cannot drift.
    return sort_unique(flat, merge, compact)


@jax.named_scope("dedup")
def fused_dedup_provenance(flat, nvalid=None, method: str | None = None,
                           merge: bool | None = None,
                           compact: str | None = None):
    """dedup_provenance with the fused lowering:
    [N] -> (uniq [N], count, uidx [N] int32). Same contract as
    ops.provenance.dedup_provenance (byte-parity-tested)."""
    if method is None:
        method = fused_dedup_method()
    if method == "callback":
        return jax.pure_callback(
            _np_dedup_provenance,
            (
                jax.ShapeDtypeStruct(flat.shape, flat.dtype),
                jax.ShapeDtypeStruct((), np.int32),
                jax.ShapeDtypeStruct(flat.shape, np.int32),
            ),
            flat,
            _nvalid_or_full(flat, nvalid),
        )
    sentinel = sentinel_for(flat.dtype)
    s, rank_back = sort_rank(flat, merge)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    keep = first & (s != sentinel)
    uid = jnp.cumsum(keep.astype(jnp.int32)) - 1
    uid = jnp.where(s != sentinel, uid, -1)
    # rank_back[j] = where input slot j landed in s; one gather replaces
    # dedup_provenance's second (origin, uid) pair sort.
    uidx = uid[rank_back]
    uniq = compact_sorted(s, keep, merge, compact)
    count = jnp.sum(keep).astype(jnp.int32)
    return uniq, count, uidx
