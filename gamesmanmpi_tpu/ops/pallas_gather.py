"""Pallas monotone-window gather (round-4; the installed Mosaic refuses it).

The TPU compiler of JAX 0.9.0 rejects this kernel ("Not implemented:
Multiple source vregs along gather dimension" on tpu.dynamic_gather over
vector<128x128xi32>), for int32 and int64 indices alike;
tests/test_tpu_compile.py pins that as a strict xfail. It runs only under
GAMESMAN_DENSE_GATHER=pallas, and in interpret mode on the CPU.

The dense engine's backward step is, per move, one byte-gather with a
globally NON-DECREASING flat index vector (solve/dense.py sorted-gather
mode builds exactly that). XLA's TPU gather treats it as random access
(~9-11 ns/element measured, microbench2 r04: 32M u32 gathers = 357 ms
regardless of table size or the sorted-indices hint); a monotone gather
can instead stream: each block of K indices touches a bounded window of
the table, so the kernel keeps two window-aligned table tiles in VMEM
and selects locally — HBM traffic becomes sequential tile reads instead
of per-element transactions.

Mosaic constraints that shaped this kernel (verified against the
installed lowering, jax/_src/pallas/mosaic/lowering.py):

* rank-1 block shapes must be whole-array or 128-multiples — the original
  per-block (1,) miss-count output could not lower; the miss count is now
  computed OUTSIDE the kernel (it depends only on idx and the window
  bases, one fused elementwise XLA pass).
* `lax.gather` lowers ONLY as 2-D `take_along_axis` with operand, indices
  and output all the same 2-D shape (tpu.dynamic_gather along dim 0 or
  dim 1). A rank-1 in-kernel `jnp.take` can never compile. The kernel
  therefore views the 2-window tile as a [R, 128] matrix and decomposes
  each offset into (row = off // 128, lane = off % 128):

      v   = take_along_axis(tile, row*, axis=0)   # sublane row-select
      out = take_along_axis(v,    lane*, axis=1)[:, 0]  # lane select

  (row*/lane* broadcast to the [R, 128] operand shape), processing R
  outputs per step so every gather operand/index shape matches.

Contract: monotone_window_gather(table_u32, idx) == table[idx] for
non-decreasing idx, EXCEPT for elements whose block spans more than one
window width — those are miss-flagged (out undefined there) and counted;
the caller sizes `window` so misses are structurally rare and falls back
to a plain gather when nmiss > 0. The dense child gathers have expansion
ratio C(L+1,n1')/C(L,n1) <= 2, so window = 4*block covers them with
margin.

idx may be int32 OR int64 (round 5): the kernel never sees the absolute
indices — BLOCK-LOCAL offsets (idx - block's window-aligned base, in
[0, 2*window)) are computed outside in one fused elementwise XLA pass
and enter Mosaic as int32. int64 inside a Mosaic kernel is a hard
no-go (the int64->int32 convert lowering recurses forever — r04 chip
session), but an int64 FLAT INDEX SPACE only needs 64-bit arithmetic
outside: the per-block window base (window units) stays under 2^31 for
any table XLA can allocate, and offsets are bounded by 2*window. This
is what unlocks gather_mode=pallas for int64-flat boards (6x6+), where
the gather win matters most (solve/dense.py:~1103, VERDICT r4 #3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _dyn_gather(x, idx, axis: int):
    """x[idx[r,c], c] (axis=0) / x[r, idx[r,c]] (axis=1) for 2-D x, idx.

    This is take_along_axis's gather, built directly so the indices stay
    int32: under jax_enable_x64 (which this package turns on for uint64
    boards) jnp.take_along_axis converts indices to int64 for its
    negative-index normalization, and Mosaic's int64->int32 convert
    lowering recurses forever (observed on-chip as a RecursionError,
    microbench2 r04). The dimension numbers below are exactly the two
    forms _gather_lowering_rule pattern-matches into tpu.dynamic_gather.
    """
    dnums = lax.GatherDimensionNumbers(
        offset_dims=(),
        collapsed_slice_dims=(axis,),
        start_index_map=(axis,),
        operand_batching_dims=(1 - axis,),
        start_indices_batching_dims=(1 - axis,),
    )
    return lax.gather(
        x, idx[..., None], dnums, (1, 1),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


@jax.named_scope("gather")
def cells_table_gather(cells, idx, valid):
    """Direct-address packed-cell gather: cells[idx] where valid, else 0.

    cells: [T] uint32 packed (value, remoteness) cells indexed by packed
    STATE (the fused backward's persistent value table — T = 2^state_bits,
    gated by ops.fused.use_value_table). idx: [...] unsigned states (may
    hold sentinel / garbage on invalid lanes). valid: [...] bool.

    The gather indices are states in frontier order — NOT monotone — so
    the monotone-window kernel above does not apply; XLA's plain gather is
    the right lowering on both backends (measured 0.015 s for 4M lanes
    from a 128 MB table on this host's CPU, vs 0.148 s for the binary
    search it replaces). Cell 0 is UNDECIDED, so the same zero doubles as
    the miss flag downstream (ops.provenance.combine_edge_cells contract).
    Kept beside the pallas kernel because it shares its one constraint:
    indices enter the gather clamped in-bounds, with validity handled by
    select — PROMISE_IN_BOUNDS-style lowering with no branch.
    """
    t = cells.shape[0]
    safe = jnp.clip(idx, 0, t - 1).astype(
        jnp.uint32 if t <= (1 << 32) else jnp.uint64
    )
    return jnp.where(valid, cells[safe], jnp.uint32(0))


def padded_table_len(m: int, window: int) -> int:
    """Table length monotone_window_gather pads to internally: a whole
    number of windows, at least two (so tile q+1 always exists). Callers
    that gather repeatedly from one table (the dense backward's w
    per-move gathers) pre-pad to this length once, making the kernel's
    internal pad a no-op."""
    return max(-(-m // window), 2) * window


def monotone_window_gather(table, idx, block: int = 2048,
                           window: int = 8192, interpret: bool = False):
    """table [M], idx [N] int32/int64 non-decreasing ->
    (out [N] table.dtype, nmiss scalar int32).

    Misses (a block spanning past its 2-window view) leave garbage in
    `out` at those positions and are counted (when nonzero, the count may
    include padding replicas of a missing tail element); callers must
    treat any nonzero nmiss as "fall back to a plain gather".
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if window % 128:
        raise ValueError(f"window must be a multiple of 128, got {window}")
    if block % 128:
        # Mosaic rank-1 block rule (module docstring): fail loudly here,
        # not with an opaque lowering error on chip.
        raise ValueError(f"block must be a multiple of 128, got {block}")
    rows = (2 * window) // 128          # [rows, 128] view of the 2-window tile
    if block % rows:
        raise ValueError(
            f"block ({block}) must be a multiple of 2*window/128 ({rows})")
    nchunk = block // rows

    n = idx.shape[0]
    npad = -n % block
    if npad:
        idx = jnp.concatenate([idx, jnp.full((npad,), idx[-1], idx.dtype)])
    nblk = idx.shape[0] // block
    # Window-aligned base of each block's view, clamped so tile q+1 exists.
    m = table.shape[0]
    padded = padded_table_len(m, window)
    nwin = padded // window
    tpad = padded - m
    if tpad:
        table = jnp.concatenate(
            [table, jnp.zeros((tpad,), table.dtype)]
        )
    starts = idx[:: block]  # [nblk] first index of each block
    # All absolute-index arithmetic happens HERE, in idx's own dtype
    # (int64 for 6x6+ flat spaces): only window-unit bases (< 2^31 for
    # any allocatable table) and 2*window-bounded offsets reach Mosaic.
    base_win = jnp.clip(starts // window, 0, nwin - 2).astype(jnp.int32)
    aligned = base_win.astype(idx.dtype) * idx.dtype.type(window)

    # The table reaches the kernel as a [padded/128, 128] matrix, reshaped
    # ONCE outside (a free XLA relayout): an in-kernel rank-1 -> rank-2
    # reshape is a Mosaic shape cast, and for packed dtypes (the dense
    # engine's u8 cells) layout inference rejects it on chip —
    # "infer-vector-layout: unsupported shape cast, vector<8192xi8> ->
    # vector<64x128xi8>" (chip session r04). With 2-D BlockSpecs the tiles
    # arrive already [window/128, 128] and no shape cast exists for ANY
    # table dtype.
    wrows = window // 128
    table2d = table.reshape(padded // 128, 128)

    # Block-local offsets, computed OUTSIDE the kernel (one fused
    # elementwise XLA pass in idx's dtype) and clamped into the tile:
    # the kernel receives only these int32 offsets, so an int64 flat
    # index space never enters Mosaic (module docstring). The miss count
    # shares the same pass — misses depend only on idx and the window
    # bases (Mosaic's rank-1 output block rule keeps it out of the
    # kernel regardless).
    off_all = idx - jnp.repeat(aligned, block)
    miss = jnp.sum(((off_all < 0) | (off_all >= 2 * window))
                   .astype(jnp.int32))
    off_i32 = jnp.clip(off_all, 0, 2 * window - 1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # window-unit tile bases
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((block,), lambda i, bw: (i,)),
            pl.BlockSpec((wrows, 128), lambda i, bw: (bw[i], 0)),
            pl.BlockSpec((wrows, 128), lambda i, bw: (bw[i] + 1, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i, bw: (i,)),
        ],
    )

    def kernel(bw_ref, off_ref, t0_ref, t1_ref, out_ref):
        # [rows, 128] view of the two window tiles. Sub-32-bit tables (the
        # dense engine's u8 cells) gather as i32 — Mosaic's dynamic_gather
        # targets 32-bit lanes; the cast back on store is exact for
        # unsigned sub-ranges.
        tile = jnp.concatenate([t0_ref[:], t1_ref[:]], axis=0)
        if tile.dtype.itemsize < 4:
            tile = tile.astype(jnp.int32)
        # All scalars below are pinned int32: under jax_enable_x64 bare
        # Python ints trace as weak int64 scalars, and ANY int64 in a
        # Mosaic kernel hits the infinitely-recursing int64->int32
        # convert lowering (see _dyn_gather's docstring). Chunks are
        # STATIC rank-1 slices of off_ref — a [nchunk, rows] reshape
        # would be another Mosaic shape cast (see the tile note above).
        c128 = jnp.int32(128)
        for k in range(nchunk):
            off = off_ref[k * rows:(k + 1) * rows]          # [rows]
            r = lax.div(off, c128)
            c = lax.rem(off, c128)
            v = _dyn_gather(
                tile, jnp.broadcast_to(r[:, None], (rows, 128)), axis=0)
            sel = _dyn_gather(
                v, jnp.broadcast_to(c[:, None], (rows, 128)), axis=1)
            out_ref[k * rows:(k + 1) * rows] = sel[:, 0].astype(out_ref.dtype)

    (out,) = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((nblk * block,), table.dtype),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(base_win, off_i32, table2d, table2d)
    # Padding lanes replicate idx[-1]; they miss iff the real tail element
    # misses, so nmiss stays 0 exactly when every real element hit (the
    # contract callers check). When nonzero it may count tail replicas.
    return out[:n], miss
