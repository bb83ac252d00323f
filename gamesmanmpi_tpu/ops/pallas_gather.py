"""Pallas monotone-window gather: the dense backward's child-cell fetch.

The dense engine's backward step gathers, per move, one u8 child cell
per position through a globally NON-DECREASING flat index vector
(solve/dense.py's monotone fill builds exactly that). XLA's TPU gather
treats it as random access: ~8 ns per lane on a TPU v5e whatever the
index order (7.65 s for the 941,638,830 lanes of a Connect-4 6x4 solve).
A monotone gather can stream instead: each block of outputs reads a
bounded stretch of the table, so this kernel DMAs two small views of the
table into VMEM per block and selects from them there (0.19 s for the
same 6x4 lanes on a v5e, every lane served from the views).

Layout. The table is packed ONCE into 32-bit words, [R, 128] int32 with
R a multiple of 8 (one (8, 128) tile): for an itemsize-s table, word
(r, l) holds elements ``r*128*e + k*128 + l`` for k < e = 4 // s, byte
lane k at bits 8*s*k. An element's (row, lane) is then (off // (128*e),
off % 128), and a tile of 8 rows covers 4096 u8 elements. Views start on
whole tiles, so every DMA is tile-aligned.

Views. Each block of B outputs (B = 1024, one [8, 128] vreg) gets two
views of ``window`` elements: view A starts at the tile holding the
block's first index, view B ends at the end of the tile holding its last
index. A block whose lanes each lie within ``window`` of its first or of
its last index is served whole from VMEM, across one large jump between
the two, or across the drop from one move's indices to the next. One grid
step covers up to MAX_STEP_BLOCKS blocks: it starts all their DMAs, then
waits for and selects each block in turn.

What Mosaic accepts (JAX 0.9.0, compiled for a described v5e:2x2):
``lax.gather`` lowers only as a 2-D ``tpu.dynamic_gather`` whose operand,
indices and output share one shape. Along the LANE axis (axis 1) it
compiles at [8, 128], [16, 128] and [64, 128] int32; along the SUBLANE
axis (axis 0) only at [8, 128] — at 16 or more rows Mosaic refuses with
"Multiple source vregs along gather dimension", which is what sank the
first version of this kernel (a sublane row-select over [128, 128]). So
the kernel selects with lane gathers only: for each view row q it
broadcasts that row over the block's sublanes, lane-gathers it by
``off % 128`` and keeps it where ``off // (128*e) == q``; a shift and a
mask then pick the element's byte out of its word. Further rules it
keeps: no int64 value enters the kernel (the int64 -> int32 convert
lowering recurses forever, and every Python int is int64 under x64, so
each scalar is pinned int32), so int64 indices become int32 offsets
into their block's two views outside it (-1 where in neither), while
int32 indices enter as they are; an int32 scalar sum is refused under
x64, so the miss count is summed in f32; table rows reach the kernel
already packed 2-D (an in-kernel reshape of a packed dtype is a shape
cast Mosaic's layout inference refuses), while the indices arrive 1-D,
in XLA's own layout, and are reshaped to [8, 128] inside; a u8 result is
stored four blocks at a time, in whole (32, 128) tiles.

Misses. A lane outside both views gets ``table[idx]`` from a fix-up that
re-gathers, with XLA, only the blocks that hold such lanes: their ids are
compacted once, then one loop trip each, so its cost is in proportion to
the missed blocks, and nothing when there are none. The kernel counts
the missed lanes of each block; their sum is returned beside the output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

TILE_ROWS = 8          # rows of one (8, 128) int32 tile: a view's alignment
LANES = 128
GATHER_BLOCK = 1024    # outputs per block: one [8, 128] int32 vreg
GATHER_WINDOW = 8192   # elements per view: two u8 tiles
MAX_STEP_BLOCKS = 64   # blocks per grid step (and DMAs in flight: 2x)


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


def _pack_words(table, window: int):
    """A 1-D table of 1-, 2- or 4-byte elements as [R, 128] int32 words
    (module docstring), padded to whole tiles and to at least one view."""
    size = jnp.dtype(table.dtype).itemsize
    if size not in (1, 2, 4):
        raise ValueError(f"window gathers need 1/2/4-byte elements, "
                         f"got {table.dtype}")
    e = 4 // size
    row_elems = LANES * e
    if window % (TILE_ROWS * row_elems):
        raise ValueError(f"window ({window}) must be a multiple of "
                         f"{TILE_ROWS * row_elems} for {table.dtype}")
    m = table.shape[0]
    rows = max(-(-m // (TILE_ROWS * row_elems)) * TILE_ROWS,
               window // row_elems)
    if rows * row_elems > m:
        table = jnp.concatenate(
            [table, jnp.zeros((rows * row_elems - m,), table.dtype)])
    if e == 1:
        return lax.bitcast_convert_type(table, jnp.int32).reshape(rows, LANES)
    # Strided row slices of a [rows*e, 128] view: XLA fuses them into one
    # pass, where a [rows, e, 128] reshape materialises the table widened
    # to u32 (4.5 GB for 6x5's largest child level).
    parts = table.reshape(rows * e, LANES)
    words = parts[0::e].astype(jnp.uint32)
    for k in range(1, e):
        words = words | (parts[k::e].astype(jnp.uint32)
                         << jnp.uint32(8 * size * k))
    return lax.bitcast_convert_type(words, jnp.int32)


def _view_bases(first, last, rows: int, view_rows: int, row_elems: int):
    """Tile-aligned first rows of each block's two views (idx's dtype in,
    int32 rows out): A at the tile of the block's first index, B ending
    with the tile of its last, both clamped inside the table."""
    dt = first.dtype
    tile = dt.type(TILE_ROWS * row_elems)
    hi = rows - view_rows
    a = jnp.clip((first // tile) * TILE_ROWS, 0, hi)
    b = jnp.clip((last // tile + 1) * TILE_ROWS - view_rows, 0, hi)
    return a.astype(jnp.int32), b.astype(jnp.int32)


def _kernel_call(bases, lidx, words, out_dtype, *, n: int, local: bool,
                 window: int, block_rows: int, step_blocks: int, interpret):
    """Run the window kernel.

    bases [nsteps, 1, 2*step_blocks] int32: each block's view A and view
    B first rows. lidx [nb*block] int32: the indices, or (local) their
    offsets into the block's two views laid end to end, -1 where in
    neither; the last grid step may run past its end. n: the real lanes.
    -> (the selected elements [nb*block_rows, 128] out_dtype, garbage
    where missed; the missed real lanes of each block
    [nsteps*step_blocks] int32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = step_blocks
    nsteps = bases.shape[0]
    per_word = 4 // jnp.dtype(out_dtype).itemsize
    row_elems = LANES * per_word
    view_rows = window // row_elems
    row_shift = row_elems.bit_length() - 1
    elem_bits = 32 // per_word
    block = block_rows * LANES
    # Every scalar below is pinned int32 (module docstring: no int64 in
    # Mosaic, and under x64 a bare Python int traces as int64).
    i32 = jnp.int32

    def select(k, step, lane_no, base_ref, idx_ref, buf, miss_ref):
        """Block k of this step: its [block_rows, 128] int32 elements."""
        ja, jb = i32(2) * k, i32(2) * k + i32(1)
        l0 = pl.multiple_of(k * i32(block), block)
        ix = idx_ref[pl.ds(l0, block)].reshape(block_rows, LANES)
        if local:
            first_a, first_b = i32(0), i32(window)
        else:
            first_a = base_ref[0, ja] * i32(row_elems)
            first_b = base_ref[0, jb] * i32(row_elems)
        rel_a, rel_b = ix - first_a, ix - first_b
        in_a = (rel_a >= 0) & (rel_a < i32(window))
        in_b = (rel_b >= 0) & (rel_b < i32(window))
        off = jnp.where(in_a, rel_a,
                        jnp.where(in_b, rel_b + i32(window), i32(0)))
        gblock = step * i32(g) + k
        real = (gblock < i32(n // block)) | (
            (gblock == i32(n // block)) & (lane_no < i32(n % block)))
        # Counted in f32 (exact up to 2^24): Mosaic refuses an int32 sum
        # to a scalar under x64.
        miss_ref[0, k] = jnp.sum(jnp.where(
            in_a | in_b | ~real, jnp.float32(0), jnp.float32(1))
        ).astype(i32)
        row = lax.shift_right_logical(off, i32(row_shift))
        lane = off & i32(LANES - 1)
        acc = jnp.zeros((block_rows, LANES), i32)
        for v, j in ((0, ja), (1, jb)):
            view = buf[j]                                 # [view_rows, 128]
            for q in range(view_rows):
                src = jnp.broadcast_to(view[q:q + 1, :], (block_rows, LANES))
                got = _dyn_gather(src, lane, axis=1)
                acc = jnp.where(row == i32(v * view_rows + q), got, acc)
        if per_word > 1:
            byte = lax.shift_right_logical(off, i32(7)) & i32(per_word - 1)
            acc = lax.shift_right_logical(acc, byte * i32(elem_bits)) \
                & i32((1 << elem_bits) - 1)
        return acc

    def kernel(base_ref, idx_ref, words_hbm, out_ref, miss_ref, buf, sem,
               tile):
        def copy(j):
            return pltpu.make_async_copy(
                words_hbm.at[pl.ds(base_ref[0, j], view_rows)],
                buf.at[j], sem.at[j])

        @pl.loop(i32(0), i32(2 * g))
        def _(j):
            copy(j).start()

        step = pl.program_id(0)
        lane_no = (lax.broadcasted_iota(i32, (block_rows, LANES), 0)
                   * i32(LANES)
                   + lax.broadcasted_iota(i32, (block_rows, LANES), 1))

        # per_word blocks a trip, a loop rather than unrolled (the kernel's
        # size sets how long every warm start spends lowering it): a
        # narrow output is stored in whole (8 * per_word, 128) tiles.
        @pl.loop(i32(0), i32(g // per_word))
        def _(t):
            @pl.loop(i32(0), i32(per_word))
            def _(u):
                k = t * i32(per_word) + u
                copy(i32(2) * k).wait()
                copy(i32(2) * k + i32(1)).wait()
                r0 = pl.multiple_of(u * i32(block_rows), TILE_ROWS)
                tile[pl.ds(r0, block_rows), :] = select(
                    k, step, lane_no, base_ref, idx_ref, buf, miss_ref)

            rows = per_word * block_rows
            r0 = pl.multiple_of(t * i32(rows), TILE_ROWS * per_word)
            out_ref[pl.ds(r0, rows), :] = tile[...].astype(out_dtype)

    rows_per_step = g * block_rows
    step_map = lambda i: (i, i32(0), i32(0))  # noqa: E731
    out, miss = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(
            (lidx.shape[0] // LANES, LANES), out_dtype),
                   jax.ShapeDtypeStruct((nsteps, 1, g), i32)),
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((None, 1, 2 * g), step_map,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((g * block,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((rows_per_step, LANES), lambda i: (i, i32(0))),
            pl.BlockSpec((None, 1, g), step_map, memory_space=pltpu.SMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((2 * g, view_rows, LANES), i32),
            pltpu.SemaphoreType.DMA((2 * g,)),
            pltpu.VMEM((per_word * block_rows, LANES), i32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="window_gather",
    )(bases, lidx, words)
    return out, miss.reshape(-1)


def _fix_misses(out, table, idx, missed, block: int):
    """Re-gather, with XLA, each block that holds a missed lane. The
    missed blocks' ids are compacted once, then each takes one loop trip:
    the cost is in proportion to the missed blocks, and nothing runs when
    none missed. out, idx: [nb*block]; missed [nb]: lanes per block."""
    nb = missed.shape[0]
    top = table.shape[0] - 1
    hit = missed > 0
    count = jnp.sum(hit, dtype=jnp.int32)

    def fix(o):
        # A stable sort, not jnp.nonzero: the TPU lowers nonzero's cumsum
        # as a reduce-window that overflows scoped VMEM at ~1k blocks.
        _, ids = lax.sort((jnp.where(hit, 0, 1).astype(jnp.int32),
                           lax.iota(jnp.int32, nb)),
                          num_keys=1, is_stable=True)

        def one_block(i, o):
            start = ids[i] * jnp.int32(block)
            rows = jnp.clip(lax.dynamic_slice(idx, (start,), (block,)),
                            0, top)
            return lax.dynamic_update_slice(o, table[rows], (start,))

        return lax.fori_loop(jnp.int32(0), count, one_block, o)

    return lax.cond(count > 0, fix, lambda o: o, out)


def monotone_window_gather(table, idx, block: int = GATHER_BLOCK,
                           window: int = GATHER_WINDOW, interpret=False):
    """table [M] (1/2/4-byte elements), idx [N] int32/int64
    non-decreasing -> (out [N] == table[idx], missed lanes: int32 scalar).

    ``block`` outputs share two views of ``window`` elements each (module
    docstring). Out-of-range indices are clamped, as XLA's gather clamps
    them. A decreasing idx stays exact, only slower (its lanes miss)."""
    if block % (TILE_ROWS * LANES):
        raise ValueError(f"block must be a multiple of "
                         f"{TILE_ROWS * LANES}, got {block}")
    table, idx = jnp.asarray(table), jnp.asarray(idx)
    words = _pack_words(table, window)
    e = 4 // jnp.dtype(table.dtype).itemsize
    row_elems = LANES * e
    view_rows = window // row_elems
    n = idx.shape[0]
    nb = -(-n // block)
    if nb * block > n:
        idx = jnp.concatenate(
            [idx, jnp.full((nb * block - n,), idx[-1], idx.dtype)])
    # Steps of g blocks, the last one possibly partial: Pallas drops its
    # writes past the end, and the kernel counts no miss there.
    g = min(MAX_STEP_BLOCKS, -(-nb // e) * e)
    nsteps = -(-nb // g)
    a, b = _view_bases(idx[::block], idx[block - 1::block],
                       words.shape[0], view_rows, row_elems)
    # int32 indices enter the kernel as they are, and it finds their view
    # offsets itself: on a TPU v5e a 6x4 solve's gather took 0.242 s so,
    # against 0.293 s with every index dtype taking the offsets pass below.
    local = idx.dtype != jnp.int32
    lidx = idx
    if local:
        # Wider indices: their offsets into the two views, found here in
        # idx's dtype so that only int32 enters the kernel.
        dt = idx.dtype

        def rel(base):
            return idx - jnp.repeat(base.astype(dt) * dt.type(row_elems),
                                    block)

        rel_a, rel_b = rel(a), rel(b)
        lidx = jnp.where(
            (rel_a >= 0) & (rel_a < window), rel_a,
            jnp.where((rel_b >= 0) & (rel_b < window), rel_b + window, -1),
        ).astype(jnp.int32)
    bases = jnp.zeros((nsteps * g, 2), jnp.int32).at[:nb].set(
        jnp.stack([a, b], axis=1))
    out_dtype = table.dtype if e > 1 else jnp.int32
    got, missed = _kernel_call(
        bases.reshape(nsteps, 1, 2 * g), lidx, words, out_dtype, n=n,
        local=local, window=window, block_rows=block // LANES,
        step_blocks=g, interpret=interpret)
    got = got.reshape(-1)
    out = got if e > 1 else lax.bitcast_convert_type(got, table.dtype)
    missed = missed[:nb]
    out = _fix_misses(out, table, idx, missed, block)
    return out[:n], jnp.sum(missed, dtype=jnp.int32)
