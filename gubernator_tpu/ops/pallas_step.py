"""Fused single-kernel decision step as a Pallas program.

One `pl.pallas_call` runs the ENTIRE bucket decision for a packed
round — in-kernel gather of the touched slots' column words, the
branch-free token/leaky update, the write-back of the new words, and
the verdict/remaining/reset pack — over state columns aliased in
place (`input_output_aliases`), so the steady-state step is ONE device
program with zero intermediate HBM round trips between its phases.

The kernel shares its math with the XLA programs, by construction:

  * the lane update is `bucket_kernel.update_lanes` — the exact
    function the fused/split XLA steps call after their gather;
  * the store encoding is `bucket_kernel.encode_slot_values` — the
    exact function `_scatter_values` scatters.

Only the irregular-access halves (gather loop in, store loop out)
are kernel-specific: per-lane dynamic reads of the 12 state columns
at the lane's slot, predicated per-lane writes back (`pl.when`), with
the same fill-0 / drop semantics as the XLA gather/scatter flags.
This is the "Ragged Paged Attention" shape (PAPERS.md): scalar-driven
irregular access feeding wide vector math.

Backend reality (PERF.md, "Bring-up on the chip"): the shared lane
math is int64 and float64, and the TPU lowering refuses 64-bit types
outright.  The probe (`pallas_step_ok`) records the refusal and
`GUBER_FUSED=auto` serves the fused XLA program — same
single-dispatch shape, same math — while `GUBER_FUSED=pallas` raises.
In interpret mode (`interpret=True`) the kernel runs as
traced jax ops under jit on ANY backend, which is how CPU CI pins the
kernel bit-equal to `models/spec.py` (tests/test_fused_parity.py)
without TPU hardware.  `GUBER_FUSED` selects the mode (core/engine).

Paged state (GUBER_PAGED, core/paging.py) needs NO kernel changes:
the engine translates logical slots to device rows (frame<<shift|row)
on the host before packing, so the packed buffer this kernel gathers
through already indexes the resident frame array — XLA, interpret,
and Pallas tiers all lower through the page table's indirection by
construction, exactly the paged-KV discipline of the attention kernel
this program is shaped after.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from gubernator_tpu.ops.bucket_kernel import (
    PACKED_IN_ROWS,
    PACKED_OUT_ROWS,
    BucketState,
    GatheredSlots,
    ProbeVerdict,
    _pack_out,
    _unpack_in,
    encode_slot_values,
    first_line,
    update_lanes,
)

_I32 = jnp.int32

N_COLS = len(BucketState._fields)


def _fused_kernel(cap: int, width: int, pin_ref, *refs):
    """Kernel body: refs = 12 state in-refs, pout ref, 12 state
    out-refs (out aliased onto in, column for column)."""
    in_cols = refs[:N_COLS]
    pout_ref = refs[N_COLS]
    out_cols = refs[N_COLS + 1 :]

    pin = pin_ref[...]
    batch, now = _unpack_in(pin)
    slot = batch.slot
    mask = slot < cap

    # ---- gather loop: per-lane dynamic reads of the column words.
    # Padding / out-of-range lanes read index 0 and mask to fill 0 —
    # identical to the XLA gather's mode="fill" contract.
    def gather_body(i, cols):
        s = slot[i]
        valid = s < cap
        idx = jnp.where(valid, s, 0)
        return tuple(
            acc.at[i].set(
                jnp.where(valid, ref[idx], jnp.zeros((), ref.dtype))
            )
            for acc, ref in zip(cols, in_cols)
        )

    init = tuple(
        jnp.zeros((width,), dtype=ref.dtype) for ref in in_cols
    )
    gathered = jax.lax.fori_loop(0, width, gather_body, init)

    # ---- the shared vector math (bit-equal to the XLA step).
    vals, resp_status, resp_rem, resp_reset = update_lanes(
        GatheredSlots(*gathered),
        mask,
        batch.algo,
        batch.behavior,
        batch.hits,
        batch.limit,
        batch.duration,
        batch.burst,
        batch.greg_duration,
        batch.greg_expire,
        now,
    )
    words = encode_slot_values(vals)

    # ---- store loop: predicated per-lane write-back (mode="drop").
    def store_body(i, _):
        s = slot[i]
        valid = s < cap
        idx = jnp.where(valid, s, 0)

        for ref, w in zip(out_cols, words):

            @pl.when(valid)
            def _(ref=ref, w=w, idx=idx, i=i):
                ref[idx] = w[i].astype(ref.dtype)

        return 0

    jax.lax.fori_loop(0, width, store_body, 0)
    pout_ref[...] = _pack_out(resp_status, resp_rem, resp_reset)


def _build_call(cap: int, width: int, dtypes, interpret: bool):
    out_shape = tuple(
        [jax.ShapeDtypeStruct((PACKED_OUT_ROWS, width), jnp.int32)]
        + [jax.ShapeDtypeStruct((cap,), dt) for dt in dtypes]
    )
    # guberlint: shapes pin [PACKED_IN_ROWS, W] int32, W on the pow2 width ladder; state columns fixed at capacity, aliased in place
    return pl.pallas_call(
        functools.partial(_fused_kernel, cap, width),
        out_shape=out_shape,
        input_output_aliases={i + 1: i + 1 for i in range(N_COLS)},
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _jitted_step(cap: int, width: int, dtypes, interpret: bool):
    call = _build_call(cap, width, dtypes, interpret)

    # guberlint: shapes state fixed at capacity; pin [PACKED_IN_ROWS, W] on the pow2 width ladder (engine warmup)
    def step(state: BucketState, pin: jax.Array):
        outs = call(pin, *state)
        return BucketState(*outs[1:]), outs[0]

    return jax.jit(step, donate_argnums=(0,))


def pallas_fused_step(
    state: BucketState, pin: jax.Array, *, interpret: bool
):
    """Drop-in twin of `bucket_kernel.fused_step`: (state, pin) →
    (new_state, packed_out), state donated/aliased in place.  One
    compiled family per (capacity, width) — widths ride the same pow2
    pad ladder as every other step program."""
    cap = state.meta.shape[0]
    width = pin.shape[1]
    dtypes = tuple(np.dtype(leaf.dtype).name for leaf in state)
    return _jitted_step(cap, width, dtypes, interpret)(state, pin)


def compile_pallas_step(cap: int, width: int = 64) -> None:
    """Lower and compile the COMPILED (non-interpret) kernel on the
    current backend; raises the compiler's own error where it refuses.
    `GUBER_FUSED=pallas` calls this so a refusal is an error, never a
    quiet switch to interpret mode."""
    from gubernator_tpu.ops.bucket_kernel import make_state

    state_sds = jax.eval_shape(lambda: make_state(cap))
    dtypes = tuple(np.dtype(l.dtype).name for l in state_sds)
    pin_sds = jax.ShapeDtypeStruct((PACKED_IN_ROWS, width), jnp.int32)
    _jitted_step(cap, width, dtypes, False).lower(
        state_sds, pin_sds
    ).compile()


@functools.lru_cache(maxsize=None)
def pallas_step_ok(cap: int, width: int = 64) -> ProbeVerdict:
    """Probe whether the COMPILED kernel lowers on this backend; the
    reason on a no is the first line of the compiler's refusal (the
    engine then serves the fused XLA program and says so).  Interpret
    mode needs no probe."""
    try:
        compile_pallas_step(cap, width)
    except Exception as e:  # noqa: BLE001 — the refusal is the verdict
        return ProbeVerdict(False, first_line(e))
    return ProbeVerdict(True, "compiled")
