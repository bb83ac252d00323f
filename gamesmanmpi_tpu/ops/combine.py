"""Parent value/remoteness combine: the RESOLVE kernel.

Reference counterpart: the negamax reduce over accumulated child results when a
position's outstanding count hits zero (src/process.py RESOLVE, SURVEY.md §3.3,
rules in §2.1.2-3). The reference reduces one parent at a time as messages
arrive; here children are regenerated aligned per parent, so the whole
frontier's combine is two masked row-reductions over a [B, M] block — the
moral equivalent of the segment-reduce in BASELINE.json's north star, with the
segmentation made trivial by alignment.
"""

import jax
import jax.numpy as jnp

from gamesmanmpi_tpu.core.values import (
    WIN,
    LOSE,
    TIE,
    MAX_REMOTENESS,
    REMOTENESS_DTYPE,
    VALUE_DTYPE,
)


@jax.named_scope("combine")
def combine_children(child_values, child_remoteness, mask):
    """Combine child results into parent (value, remoteness).

    child_values: [B, M] uint8 (child-perspective values).
    child_remoteness: [B, M] int32.
    mask: [B, M] bool — True where a real child exists.

    Rules (SURVEY.md §2.1.2-3):
      value:  WIN if any child LOSE; else TIE if any child TIE; else LOSE.
              (Zero children -> vacuous LOSE with remoteness 0; the engine only
              feeds non-primitive positions here, and a non-primitive position
              with no moves is a game-definition error — the engines count such
              rows in their consistency counter and --paranoid raises on it.)
      remoteness: WIN  -> 1 + min over LOSE children
                  LOSE -> 1 + max over all children
                  TIE  -> 1 + max over TIE children
    Returns (values [B] uint8, remoteness [B] int32).
    """
    cv = child_values
    cr = child_remoteness.astype(REMOTENESS_DTYPE)

    lose = mask & (cv == LOSE)
    tie = mask & (cv == TIE)

    any_lose = jnp.any(lose, axis=-1)
    any_tie = jnp.any(tie, axis=-1)

    values = jnp.where(
        any_lose,
        jnp.uint8(WIN),
        jnp.where(any_tie, jnp.uint8(TIE), jnp.uint8(LOSE)),
    ).astype(VALUE_DTYPE)

    win_rem = 1 + jnp.min(jnp.where(lose, cr, MAX_REMOTENESS), axis=-1)
    lose_rem = 1 + jnp.max(jnp.where(mask, cr, -1), axis=-1)
    tie_rem = 1 + jnp.max(jnp.where(tie, cr, -1), axis=-1)

    remoteness = jnp.where(any_lose, win_rem, jnp.where(any_tie, tie_rem, lose_rem))
    return values, remoteness.astype(REMOTENESS_DTYPE)
