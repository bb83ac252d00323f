"""Merge-ladder sort: batched row sorts + bitonic merge stages, pure XLA.

Why this exists: the BFS engines are sort-bound on TPU — XLA's sort ran at
~0.85 GB/s operand throughput on the v5e (tools/microbench.py), far below
the chip's ~819 GB/s HBM roofline. A bitonic MERGE of two sorted arrays is
log2(n) compare-exchange stages, each a pure elementwise min/max pass that
XLA fuses and runs at memory bandwidth — no sorting network. Sorting via
"row-sort small chunks, then merge pairwise" therefore replaces most of
the sort network with elementwise passes:

  sort [R, C] rows (XLA batched sort, C sized so a row is cheap)
  repeat log2(R) times: merge row pairs [R, C] -> [R/2, 2C]

Total stage count ~ log2(R) * log2(N) elementwise passes vs the sort
network's ~log2(N)^2/2 — and the passes are cheaper. Whether that wins on
the real chip is an empirical question (tools/microbench2.py measures
both); the engines adopt it behind GAMESMAN_SORT=merge, default XLA sort,
so the flag can flip on measurement without code changes.

Correctness notes: inputs are padded to a power-of-two length with the
all-ones sentinel (which sorts last, matching the engines' padding
convention); merging keys with an i32 payload uses compare-on-key
exchanges of both arrays.

Known limitation (2026-07-30): with GAMESMAN_SORT=merge set for an entire
test-suite process, XLA's CPU compiler segfaulted twice, reproducibly,
while compiling an UNRELATED backend-independent kernel late in the run
(tests/test_symmetry.py chomp case; the same test passes in isolation and
the whole suite passes under the default backend). The merge ladder's
unrolled stage chain produces much larger HLO than lax.sort, and
compiling many of those earlier in the process appears to leave the CPU
compiler in a state where a later compile crashes — an upstream stress
bug, not a correctness issue (every equivalence test passes). Treat
GAMESMAN_SORT=merge as a per-process experimental flag; the default
stays "xla" until the chip measurement decides (docs/CHIP_PLAN.md).

MEASURED no-go (chip session r04, v5e): merge_sort u32 [32M] =
1.13-1.16 s across row sizes vs jnp.sort's 0.15 s — the ladder LOSES
7.5x. The premise failed on silicon: XLA's one-shot sort ran at
1.76 GB/s (not the 0.85 GB/s round-3 figure), while the ladder's many
full-array elementwise stages each pay real HBM traffic and their sum
dwarfs the sort network. The u32+payload variant additionally failed to
compile (HTTP 500 from the compile service of that session). The flag
stays for CPU experiments; do NOT flip it for accelerators.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.utils.env import env_int, env_str


def use_merge_sort() -> bool:
    """Engines consult this flag at trace time (GAMESMAN_SORT=merge)."""
    return env_str("GAMESMAN_SORT", "xla") == "merge"


def backend_key():
    """Cache-key element describing the resolved sort backend.

    Includes the row width when the merge backend is active: it is read at
    trace time too (see _row_width), so two row settings are two different
    programs. (A GAMESMAN_SORT_ROW flip between scheduling a background
    compile and its worker tracing can still race — flip row widths only
    at process start or with inline-jitted kernels, as tools/microbench2
    does.)
    """
    if not use_merge_sort():
        return "xla"
    return ("merge", env_str("GAMESMAN_SORT_ROW", "2048"))


def _pay_max(dtype):
    """Largest value of an integer payload dtype (pad marker)."""
    return np.iinfo(np.dtype(dtype)).max


def _row_width(n: int) -> int:
    """Base row width for the row-sort stage (power of two).

    GAMESMAN_SORT_ROW tunes it; default 2048 keeps each row's sort network
    shallow while leaving most of the work to the merge ladder.
    """
    w = env_int("GAMESMAN_SORT_ROW", 2048)
    w = 1 << max(int(w).bit_length() - 1, 0)  # round down to a power of two
    return max(min(w, n), 1)


def _merge_rows(a, b, *payloads_ab):
    """Merge sorted rows pairwise: a, b [R, C] -> [R, 2C] sorted rows.

    concat(a, reverse(b)) is bitonic per row; log2(2C) compare-exchange
    stages sort it. With payloads, exchanges compare (key, first payload)
    lexicographically: merge_sort pads with MAX payloads under sentinel
    keys, and the tie-break guarantees every REAL (sentinel, payload) pair
    sorts before the padding — without it, truncating back to the input
    length could keep fake pad pairs and drop real ones (which would
    corrupt expand_provenance's origin permutation under
    GAMESMAN_SORT=merge).
    payloads_ab: (pa, pb) pairs following a/b.
    """
    R, C = a.shape
    z = jnp.concatenate([a, b[:, ::-1]], axis=1)  # [R, 2C] bitonic rows
    ps = [
        jnp.concatenate([pa, pb[:, ::-1]], axis=1)
        for pa, pb in zip(payloads_ab[0::2], payloads_ab[1::2])
    ]
    n = 2 * C
    s = n // 2
    while s >= 1:
        y = z.reshape(R, -1, 2, s)
        k0, k1 = y[:, :, 0, :], y[:, :, 1, :]
        if ps:
            q0 = ps[0].reshape(R, -1, 2, s)
            lo_is_first = (k0 < k1) | (
                (k0 == k1) & (q0[:, :, 0, :] <= q0[:, :, 1, :])
            )
        else:
            lo_is_first = k0 <= k1
        lo = jnp.where(lo_is_first, k0, k1)
        hi = jnp.where(lo_is_first, k1, k0)
        z = jnp.stack([lo, hi], axis=2).reshape(R, n)
        new_ps = []
        for p in ps:
            q = p.reshape(R, -1, 2, s)
            plo = jnp.where(lo_is_first, q[:, :, 0, :], q[:, :, 1, :])
            phi = jnp.where(lo_is_first, q[:, :, 1, :], q[:, :, 0, :])
            new_ps.append(jnp.stack([plo, phi], axis=2).reshape(R, n))
        ps = new_ps
        s //= 2
    return (z, *ps)


def sort1(x, merge: bool | None = None):
    """Flag-dispatched key sort.

    merge=None reads the env flag AT TRACE TIME — fine for direct/eager
    callers. Kernel builders must instead resolve use_merge_sort() at
    BUILD time and pass it explicitly: background precompile workers trace
    later, and an ambient read there could disagree with the cache key
    sampled when the kernel was scheduled.
    """
    if use_merge_sort() if merge is None else merge:
        return merge_sort(x)
    return jnp.sort(x)


def sort_with_payload(keys, payload, merge: bool | None = None):
    """Flag-dispatched (keys, payload) sort by keys (see sort1 re: merge).

    Integer payload only; with the merge backend, signed non-negative keys
    are viewed as unsigned (order-preserving) so sentinel padding works.
    """
    if not (use_merge_sort() if merge is None else merge):
        import jax

        return jax.lax.sort((keys, payload), num_keys=1, is_stable=False)
    kd = np.dtype(keys.dtype)
    if kd.kind == "i":
        # Permutation/index keys are non-negative; the unsigned view keeps
        # their order and gives merge_sort a valid sentinel.
        k2, p2 = merge_sort(keys.astype(np.dtype(f"u{kd.itemsize}")),
                            payload)
        return k2.astype(keys.dtype), p2
    return merge_sort(keys, payload)


def sort_rank(x, merge: bool | None = None):
    """Key sort that also reports where every input slot landed.

    Returns (sorted [N], rank_back [N] int32): rank_back[j] is the index
    of input slot j within the sorted output. One (key, origin) pair sort
    plus one permutation-inverting scatter — the sorted origin column is a
    permutation of iota, so `zeros.at[origin].set(iota)` inverts it in one
    O(n) pass. This is the fused half of the rank/sort+dedup kernel
    (ops/fused.fused_dedup_provenance 'scatterinv'): dedup_provenance
    reconstructs the same mapping with a SECOND (origin, uid) pair sort,
    i.e. a full extra ~log2(n)-pass network of HBM traffic per level.

    merge: sort-backend flag, resolved at BUILD time by kernel builders
    (see sort1).
    """
    import jax

    origin = jax.lax.iota(jnp.int32, x.shape[0])
    s, o = sort_with_payload(x, origin, merge)
    rank_back = jnp.zeros_like(origin).at[o].set(origin)
    return s, rank_back


def merge_sort(x, *payloads):
    """Sort [N] keys ascending (with optional same-length payloads carried).

    Pads to a power of two with the key dtype's sentinel; returns arrays of
    the ORIGINAL length. Stable ordering is NOT guaranteed (the engines'
    uses — dedup, permutation routing — don't need stability).
    """
    n = x.shape[0]
    n2 = 1 << max((n - 1).bit_length(), 0)
    sentinel = sentinel_for(np.dtype(x.dtype))
    if n2 != n:
        pad = jnp.full((n2 - n,), sentinel, x.dtype)
        x = jnp.concatenate([x, pad])
        # MAX payload under the sentinel key + the merge stages' payload
        # tie-break => padding sorts strictly after every real pair, so
        # truncation back to n can only ever drop padding.
        payloads = tuple(
            jnp.concatenate([
                p,
                jnp.full((n2 - n,), _pay_max(p.dtype), p.dtype),
            ])
            for p in payloads
        )
    C = _row_width(n2)
    R = n2 // C
    rows = [x.reshape(R, C)] + [p.reshape(R, C) for p in payloads]
    if len(rows) == 1:
        sorted_rows = [jnp.sort(rows[0], axis=-1)]
    else:
        # Two sort keys: the merge stages' compare-exchange breaks key ties
        # on the first payload, which is only correct if its inputs are
        # lex-sorted the same way (comparator networks need one total
        # order end to end).
        import jax

        sorted_rows = list(
            jax.lax.sort(tuple(rows), dimension=-1, num_keys=2,
                         is_stable=False)
        )
    while R > 1:
        args = []
        for r in sorted_rows:
            args += [r[0::2], r[1::2]]
        merged = _merge_rows(args[0], args[1], *args[2:])
        sorted_rows = list(merged)
        R //= 2
    out = tuple(r.reshape(-1)[:n] for r in sorted_rows)
    return out[0] if not payloads else out
