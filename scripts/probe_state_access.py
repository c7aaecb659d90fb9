"""Step 0 of ISSUE 25: which access of a step is the pass over the table?

At 100 M rows a step costs 14 ms whatever its width (ledger, PR 24).
This times, on the chip, one `u32[PROBE_ROWS]` column (100 M), slots
sorted and unique as the engine sends them, the column donated where it
is written, widths 64 and 1,024:

  a  the word gather alone                (sorted + unique hints)
  b  the word scatter alone               (same hints, donated)
  b1 / b2  the word scatter with one hint (sorted alone, unique alone)
  c  a row gather of every lane's row     ([N/128, 128] view, sorted)
  c1 a row gather of the distinct rows    (sorted + unique)
  d  a row scatter of the distinct rows   (donated 2-D view)
  e  a and b without the hints

then (`probe_crossover`) b against e's scatter at 1 M, 8 M, 25 M and
100 M rows and widths 64, 1,024 and 8,192 — what
`ops/bucket_kernel.py` `_scatter_hints` chooses between, and where —
and the step programs themselves (`_collapsed_step_core` at 1,024,
`_uniform_step_core` at 64) with each form of the scatter, and reads
from the compiled collapsed step what touches the table.  Every timing
is K iterations inside ONE program (a scan over K index sets), so a
dispatch's host cost is not in it.  One JSON line; the full record also
lands in chiprun_out/probe_state_access.json.  Exits non-zero anywhere
but on a TPU.

    chiprun -- python3 scripts/probe_state_access.py
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import gubernator_tpu  # noqa: F401 — x64 config
from gubernator_tpu.ops import bucket_kernel as bk

N = int(os.environ.get("PROBE_ROWS", "100000000"))
# PROBE_REHEARSE=1 debugs the script off the chip (tiny PROBE_ROWS); its
# numbers are not step 0's.
_REHEARSE = bool(os.environ.get("PROBE_REHEARSE"))
K = 16
WIDTHS = (64, 1024)
_FLAGS = dict(indices_are_sorted=True, unique_indices=True)


# -- the accesses -------------------------------------------------------------


def word_gather(col, slot, **flags):
    return col.at[slot].get(mode="fill", fill_value=0, **flags)


def word_scatter(col, slot, v, **flags):
    return col.at[slot].set(v, mode="drop", **flags)


def row_gather_every_lane(col, slot):
    rows = col.reshape(-1, 128).at[slot >> 7].get(
        mode="fill", fill_value=0, indices_are_sorted=True
    )
    return jnp.sum(
        jnp.where((slot & 127)[:, None] == jnp.arange(128)[None, :], rows, 0),
        axis=1, dtype=col.dtype,
    )


def row_gather_distinct(col, urow):
    return col.reshape(-1, 128).at[urow].get(
        mode="fill", fill_value=0, **_FLAGS
    )


def distinct_rows(slots):
    """The distinct 128-word rows of sorted slots, then distinct
    ascending out-of-range rows up to the width."""
    rows = np.unique(slots >> 7)
    pad = np.arange(len(slots) - len(rows), dtype=np.int32) + (1 << 30)
    return np.concatenate([rows, pad]).astype(np.int32)


def row_scatter_distinct(col, urow, rows):
    return (
        col.reshape(-1, 128)
        .at[urow].set(rows, mode="drop", **_FLAGS)
        .reshape(-1)
    )


def _slots(rng, w, kind, n=None):
    """K sorted, unique index sets of width w (int32 [K, w]) into n rows."""
    n = n or N
    out = np.empty((K, w), dtype=np.int32)
    for k in range(K):
        if kind == "uniform":  # every lane a row of its own
            s = rng.permutation(np.unique(rng.integers(0, n, size=2 * w)))[:w]
        else:  # "skewed": what sequential interning under Zipf looks like
            s = np.unique((rng.pareto(0.9, size=4 * w) * 64).astype(np.int64))
            s = s[s < n][:w]
            if len(s) < w:
                extra = np.setdiff1d(rng.integers(0, n, size=2 * w), s)
                s = np.concatenate([s, extra[: w - len(s)]])
        out[k] = np.sort(s).astype(np.int32)
    return out


def _timed(fn, *args, reps=3):
    """Seconds a call of the jitted `fn`, after one warm-up call.  The
    first argument is donated and threaded through."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        args = (out[0],) + args[1:]
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def time_reads(read, col, idx):
    """us an iteration of `read(col, idx[k])`, K in one program."""

    def prog(acc, col, idx):
        def body(a, i):
            return a ^ jnp.sum(read(col, i), dtype=jnp.uint32), None

        return lax.scan(body, acc, idx)[0], None

    f = jax.jit(prog, donate_argnums=(0,))
    dt, _ = _timed(f, jnp.zeros((), jnp.uint32), col, idx)
    return dt / K * 1e6


def time_writes(write, col, idx, vals):
    """us an iteration of `col = write(col, idx[k], vals[k])`; returns
    (us, the column after) — the column is donated."""

    def prog(col, idx, vals):
        def body(c, iv):
            return write(c, *iv), None

        return lax.scan(body, col, (idx, vals))[0], None

    f = jax.jit(prog, donate_argnums=(0,))
    dt, out = _timed(f, col, idx, vals)
    return dt / K * 1e6, out[0]


def probe_accesses(rec):
    rng = np.random.default_rng(25)
    col = jnp.asarray(
        rng.integers(0, 2**32, size=N, dtype=np.uint32)
    )
    host = np.asarray(col)
    for w in WIDTHS:
        r = rec.setdefault(f"w{w}", {})
        slots = _slots(rng, w, "uniform")
        idx = jnp.asarray(slots)
        urows = jnp.asarray(np.stack([distinct_rows(s) for s in slots]))
        vals = jnp.asarray(rng.integers(0, 2**32, size=(K, w), dtype=np.uint32))
        rows = jnp.asarray(
            rng.integers(0, 2**32, size=(K, w, 128), dtype=np.uint32))
        want = host[slots[0]]
        agree = r.setdefault("agree", {})

        def attempt(key, thunk):
            try:
                r[key] = thunk()
            except Exception as e:  # noqa: BLE001 — a refusal is a finding
                r[key] = bk.first_line(e)

        def same(key, thunk):
            try:
                agree[key] = bool((np.asarray(thunk()) == want).all())
            except Exception as e:  # noqa: BLE001
                agree[key] = bk.first_line(e)

        same("a", lambda: word_gather(col, idx[0], **_FLAGS))
        same("c", lambda: row_gather_every_lane(col, idx[0]))
        same("e", lambda: word_gather(col, idx[0]))

        attempt("a_word_gather_us", lambda: time_reads(
            lambda c, i: word_gather(c, i, **_FLAGS), col, idx))
        attempt("c_row_gather_every_lane_us", lambda: time_reads(
            row_gather_every_lane, col, idx))
        attempt("c1_row_gather_distinct_us", lambda: time_reads(
            row_gather_distinct, col, urows))
        attempt("e_word_gather_noflags_us", lambda: time_reads(
            word_gather, col, idx))

        def writes(key, write, i, v):
            nonlocal col
            try:
                r[key], col = time_writes(write, col, i, v)
            except Exception as e:  # noqa: BLE001
                r[key] = bk.first_line(e)
                if col.is_deleted():
                    col = jnp.asarray(host)

        writes("b_word_scatter_us",
               lambda c, i, v: word_scatter(c, i, v, **_FLAGS), idx, vals)
        writes("b1_word_scatter_sorted_alone_us",
               lambda c, i, v: word_scatter(c, i, v, indices_are_sorted=True),
               idx, vals)
        writes("b2_word_scatter_unique_alone_us",
               lambda c, i, v: word_scatter(c, i, v, unique_indices=True),
               idx, vals)
        writes("d_row_scatter_distinct_us", row_scatter_distinct, urows, rows)
        writes("e_word_scatter_noflags_us", word_scatter, idx, vals)
        # the last writer was the scatter without hints: its words must
        # be there
        want = np.asarray(vals[K - 1])
        same("e_scatter", lambda: word_gather(col, idx[K - 1], **_FLAGS))
        host = np.asarray(col)
    del col


CROSSOVER_ROWS = (1 << 20, 1 << 23, 25_000_000, 100_000_000)
CROSSOVER_WIDTHS = (64, 1024, 8192)


def probe_crossover(rec):
    """us a word scatter with the hints (`pass`) and without (`loop`),
    and what `_scatter_hints` chooses there."""
    rng = np.random.default_rng(27)
    out = rec.setdefault("crossover", {})
    for n in CROSSOVER_ROWS if not _REHEARSE else (N,):
        col = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
        for w in CROSSOVER_WIDTHS:
            idx = jnp.asarray(_slots(rng, w, "uniform", n))
            vals = jnp.asarray(
                rng.integers(0, 2**32, size=(K, w), dtype=np.uint32))
            r = out[f"rows{n}.w{w}"] = {
                "rule": "pass" if all(bk._scatter_hints(n, w).values()) else "loop"}
            r["pass_us"], col = time_writes(
                lambda c, i, v: word_scatter(c, i, v, **_FLAGS),
                col, idx, vals)
            r["loop_us"], col = time_writes(word_scatter, col, idx, vals)
        del col


def _pins(rng, w, kind, collapsed):
    slots = _slots(rng, w, kind)
    pins = []
    for k in range(K):
        s = slots[k][: w - w // 32]  # a few padding lanes, as served
        n = len(s)
        f = dict(
            algo=rng.integers(0, 2, n), behavior=np.zeros(n, np.int64),
            hits=np.ones(n, np.int64), limit=np.full(n, 100, np.int64),
            duration=np.full(n, 60_000, np.int64),
            burst=np.full(n, 100, np.int64),
            gdur=np.zeros(n, np.int64), gexp=np.zeros(n, np.int64),
        )
        now = 1_700_000_000_000 + k
        if collapsed:
            pins.append(bk.pack_collapsed_host(
                w, now, N, s, rng.integers(1, 4, n).astype(np.int64),
                tuple(f.values()), np.arange(n, dtype=np.int32),
                np.zeros(n, np.int32)))
        else:
            pins.append(bk.pack_uniform_host(
                w, now, N, s, 0, 0, 1, 100, 60_000, 100))
    return jnp.asarray(np.stack(pins))


def probe_steps(rec):
    """us a step, K steps scanned in one program, with each form of the
    scatter; the state is donated and threaded through."""
    rng = np.random.default_rng(26)
    state = bk.make_state(N)
    shipped = bk._SCATTER_PASS_ROWS_PER_LANE
    for name, core, w, collapsed in (
        ("collapsed_w1024", bk._collapsed_step_core, 1024, True),
        ("uniform_w64", bk._uniform_step_core, 64, False),
    ):
        # slots as sequential interning under Zipf leaves them: the
        # served shape (uniform slots read 15 % more in the first run)
        for kind in ("skewed",):
            pins = _pins(rng, w, kind, collapsed)
            for form, per_lane in (("loop", 0), ("pass", 1 << 40)):
                bk._SCATTER_PASS_ROWS_PER_LANE = per_lane
                try:
                    # (a fresh body each time: scan caches a body's
                    # trace by the function, form and all)
                    f = jax.jit(
                        lambda st, pins: lax.scan(
                            lambda s, p: core(s, p), st, pins),
                        donate_argnums=(0,),
                    )
                    hinted = len(re.findall(
                        r"stablehlo\.scatter[^\n]*unique_indices = true",
                        f.lower(state, pins).as_text()))
                    assert hinted == (12 if form == "pass" else 0), hinted
                    dt, out = _timed(f, state, pins)
                finally:
                    bk._SCATTER_PASS_ROWS_PER_LANE = shipped
                state = out[0]
                rec.setdefault("step_us", {})[
                    f"{name}.{kind}.{form}"] = dt / K * 1e6
    rec["step_form_shipped"] = {
        f"w{w}": "pass" if all(bk._scatter_hints(N, w).values()) else "loop"
        for w in WIDTHS
    }
    del state


def read_compiled(rec):
    """What the compiled collapsed step does to the table: the ops whose
    operand or result is a whole column, by opcode and result shape."""
    state = jax.eval_shape(lambda: bk.make_state(N))
    pin = jax.ShapeDtypeStruct((bk.COLLAPSED_IN_ROWS, 1024), jnp.int32)
    compiled = bk.collapsed_step.lower(state, pin).compile()
    text = compiled.as_text()
    whole = (f"[{N}]",)
    ops: dict[str, int] = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+?)(?:\{[^ ]*)? ([\w-]+)\(", line)
        if not m or not any(s in line for s in whole):
            continue
        if m.group(2) in ("parameter", "get-tuple-element", "tuple"):
            continue
        key = f"{m.group(2)} -> {m.group(1)}"
        ops[key] = ops.get(key, 0) + 1
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    rec["compiled_collapsed_step"] = {
        "table_ops": ops,
        "column_copied": any(k.startswith("copy") for k in ops),
        "scatters_hinted": sum(
            "indices_are_sorted=true" in ln or "unique_indices=true" in ln
            for ln in scatters),
        "scatters": len(scatters),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
    }
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/collapsed_step_100m.hlo.txt", "w") as fh:
        fh.write(text)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not _REHEARSE:
        print(json.dumps({"ok": False, "reason": f"platform {dev.platform}: "
                          "step 0 is a chip measurement"}))
        return 1
    rec = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rows": N, "iterations_a_program": K,
    }
    # PROBE_PARTS=probe_steps runs the named parts alone (a second look
    # at the steps costs no second pass over the accesses).
    only = os.environ.get("PROBE_PARTS", "").split(",")
    for part in (read_compiled, probe_accesses, probe_crossover, probe_steps):
        if only != [""] and part.__name__ not in only:
            continue
        try:
            part(rec)
        except Exception as e:  # noqa: BLE001 — the refusal is a finding
            rec[f"{part.__name__}_error"] = bk.first_line(e)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_state_access.json", "w") as fh:
        json.dump(rec, fh, indent=1)
    print(json.dumps(rec))
    return 0 if not any(k.endswith("_error") for k in rec) else 2


if __name__ == "__main__":
    sys.exit(main())
