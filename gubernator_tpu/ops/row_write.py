"""The step's write by rows: each 128-word row of a state column that a
step touched is written once, by one DMA, from one Pallas kernel for
all the columns.

A column of `rows` words (`rows % 128 == 0`) is viewed as
`[rows / 128, 128]` — on a v5e a bitcast of the `[rows]` array, no
copy.  The step's slots are sorted, unique, and padded with distinct
out-of-range slots, so lanes that share a row are adjacent: the first
lane of each row is its *writer*.  XLA gathers every lane's row,
sets each lane's word in its writer's copy (a small scatter, sorted
and unique over `[lanes, 128]`), and the kernel DMAs each writer's
row over the column where it lies (`input_output_aliases`): O(rows
touched) a step, where XLA's own scatter into a large column is a
serial loop over the lanes (`bucket_kernel._scatter_form`).

Only a TPU lowers the kernel; `interpret=True` runs it anywhere (the
tests' parity against `.at[slot].set(mode="drop")`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW = 128
# Writers whose DMAs may be in flight at once: a lane's twelve copies
# are waited for this many lanes after they start.
_IN_FLIGHT = 256


def writers(slot: jax.Array, rows: int) -> tuple[jax.Array, jax.Array]:
    """(row of each lane's writer in the `[lanes, 128]` block — `lanes`
    for a padding lane —, the destination row a writer lane DMAs to or
    -1) for slots sorted, unique, out-of-range padding."""
    lanes = slot.shape[0]
    live = slot < rows
    row = slot >> 7
    first = live & jnp.concatenate(
        [jnp.ones((1,), bool), row[1:] != row[:-1]]
    )
    lane = jnp.arange(lanes, dtype=jnp.int32)
    writer = jax.lax.cummax(jnp.where(first, lane, 0))
    return jnp.where(live, writer, lanes), jnp.where(first, row, -1)


def new_rows(
    column: jax.Array, slot: jax.Array, word: jax.Array, writer: jax.Array
) -> jax.Array:
    """`[lanes, 128]`: row `writer[i]` is lane i's row of `column` with
    the words of every lane of that row set; other rows are unused."""
    lanes = slot.shape[0]
    block = column.reshape(-1, ROW)
    live = writer < lanes
    old = block.at[jnp.where(live, slot >> 7, 0)].get(
        mode="promise_in_bounds", indices_are_sorted=True
    )
    # (writer, word) ascends with the slot; a padding lane's index lies
    # past the block, distinct and ascending, and is dropped
    at = jnp.where(
        live,
        writer * ROW + (slot & (ROW - 1)),
        lanes * ROW + jnp.arange(lanes, dtype=jnp.int32),
    )
    return (
        old.reshape(-1)
        .at[at]
        .set(word, mode="drop", indices_are_sorted=True, unique_indices=True)
        .reshape(lanes, ROW)
    )


# guberlint: shapes dst [W] int32 (SMEM), new rows [W, 128] and columns [rows / 128, 128] a column: W on the step's width ladder, rows the table's
def _kernel(dst_ref, *refs):
    n = len(refs) // 3
    news, columns, sems = refs[:n], refs[2 * n : 3 * n], refs[3 * n]
    lanes = news[0].shape[0]

    def copy(i, k):
        return pltpu.make_async_copy(
            news[k].at[pl.ds(i, 1)],
            columns[k].at[pl.ds(dst_ref[i], 1)],
            sems.at[k],
        )

    def start(i):
        @pl.when(dst_ref[i] >= 0)
        def _():
            for k in range(n):
                copy(i, k).start()

    def wait(i):
        @pl.when(dst_ref[i] >= 0)
        def _():
            for k in range(n):
                copy(i, k).wait()

    def body(i, carry):
        start(i)

        @pl.when(i >= _IN_FLIGHT)
        def _():
            wait(i - _IN_FLIGHT)

        return carry

    jax.lax.fori_loop(0, lanes, body, None)
    jax.lax.fori_loop(
        max(lanes - _IN_FLIGHT, 0), lanes, lambda i, c: wait(i), None
    )


def write_rows(
    columns: tuple, slot: jax.Array, words: tuple, *, interpret: bool = False
) -> tuple:
    """`column.at[slot].set(word, mode="drop")` for every column, by
    rows.  Every column has the same `rows`, a multiple of 128; `slot`
    is sorted, unique, and out-of-range only for its padding."""
    rows = columns[0].shape[0]
    writer, dst = writers(slot, rows)
    news = [
        new_rows(c, slot, w, writer)
        for c, w in zip(columns, words)
    ]
    blocks = [c.reshape(-1, ROW) for c in columns]
    n = len(columns)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(b.shape, b.dtype) for b in blocks],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[any_space] * (2 * n),
            out_specs=[any_space] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
        ),
        # operand 0 is `dst`, then the new rows, then the columns
        input_output_aliases={1 + n + k: k for k in range(n)},
        interpret=interpret,
        name="row_write",
    )
    # traced with 32-bit scalars: Mosaic takes no 64-bit loop index
    with jax.enable_x64(False):
        out = call(dst.astype(jnp.int32), *news, *blocks)
    return tuple(b.reshape(rows) for b in out)
