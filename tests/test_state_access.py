"""The seam between the step programs and the state: `gather_slots`
reads and `_scatter_values` writes the words of a step's slots, and the
scatter promises XLA sorted, unique indices only where the streaming
pass that promise buys is cheaper than a loop over the lanes; lowered
for a TPU, a table too large for the pass is written by rows instead
(ops/bucket_kernel.py `_scatter_form`; tests/test_row_write.py).

The oracle is numpy indexing for the seam functions alone, and the same
step program traced with the other form of the scatter for the step
forms end to end — on random state words, so that a lane read from or
written to the wrong word cannot hide behind zeros."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops import bucket_kernel as bk

NOW = 1_700_000_000_000

# `_SCATTER_PASS_ROWS_PER_LANE` that forces one form whatever the shapes
FORMS = {"loop": 0, "pass": 1 << 40}


@pytest.fixture(params=sorted(FORMS))
def form(request, monkeypatch):
    monkeypatch.setattr(
        bk, "_SCATTER_PASS_ROWS_PER_LANE", FORMS[request.param]
    )
    return request.param


def fresh(fn):
    """jax caches a trace by the function traced (`jit`, `scan` and
    `lower` alike): a form set after the first trace of `fn` itself
    would never be seen."""
    return lambda *args: fn(*args)


def scatter_forms(program, *args) -> set:
    """The forms of the scatters in the lowered program."""
    hints = re.findall(
        r"indices_are_sorted = (\w+)[^\n]*?unique_indices = (\w+)",
        "\n".join(
            line
            for line in program.lower(*args).as_text().splitlines()
            if "stablehlo.scatter" in line
        ),
    )
    return {
        {("true", "true"): "pass", ("false", "false"): "loop"}[h]
        for h in hints
    }


def random_state(cap: int, seed: int = 0) -> bk.BucketState:
    rng = np.random.default_rng(seed)
    return bk.BucketState(
        *(
            jnp.asarray(
                rng.integers(0, 2**31 - 1, size=cap).astype(col.dtype)
            )
            for col in bk.make_state(cap)
        )
    )


def padded(slots, width: int, cap: int) -> np.ndarray:
    """Sorted unique slots, then distinct ascending out-of-range padding
    (what every host packer sends)."""
    slots = np.asarray(sorted(slots), dtype=np.int32)
    pad = np.arange(cap, cap + width - len(slots), dtype=np.int32)
    return np.concatenate([slots, pad])


# name -> (capacity, live slots, width)
SEAM_CASES = {
    "two_lanes_of_one_row": (1024, [130, 200], 64),
    "seventeen_lanes_of_one_row": (1024, range(300, 317), 64),
    "all_128_lanes_of_one_row": (1024, range(256, 384), 128),
    "segment_straddles_a_row_boundary": (1024, range(120, 136), 64),
    "first_and_last_row": (1024, [0, 1, 127, 896, 1022, 1023], 64),
    "first_and_last_word": (128, [0, 127], 64),
    "every_lane_padding": (1024, [], 64),
    "width_64_one_live_lane": (1024, [777], 64),
    "full_width_no_padding": (256, range(0, 256, 4), 64),
    "many_rows_many_lanes": (
        128 * 40,
        np.random.default_rng(3).choice(128 * 40, 700, replace=False),
        1024,
    ),
    "capacity_not_a_multiple_of_128": (1000, [0, 1, 2, 127, 128, 999], 64),
    "capacity_4": (4, [0, 3], 64),
}


@pytest.mark.parametrize("name", sorted(SEAM_CASES))
def test_gather_slots_reads_the_words_numpy_reads(name):
    cap, live, width = SEAM_CASES[name]
    state = random_state(cap)
    slot = padded(live, width, cap)
    got = jax.jit(bk.gather_slots)(state, jnp.asarray(slot))
    inside = slot < cap
    for col, words in zip(state, got):
        want = np.where(inside, np.asarray(col)[np.where(inside, slot, 0)], 0)
        np.testing.assert_array_equal(np.asarray(words), want)


@pytest.mark.parametrize("name", sorted(SEAM_CASES))
def test_scatter_values_writes_the_words_numpy_writes(name, form, monkeypatch):
    cap, live, width = SEAM_CASES[name]
    state = random_state(cap)
    slot = padded(live, width, cap)
    rng = np.random.default_rng(1)
    words = tuple(rng.integers(0, 2**31 - 1, size=width) for _ in state)
    # the seam alone: store these words as they are
    monkeypatch.setattr(bk, "encode_slot_values", lambda vals: vals)
    program = jax.jit(fresh(bk._scatter_values))
    args = (state, jnp.asarray(slot), tuple(map(jnp.asarray, words)))
    assert scatter_forms(program, *args) == {form}
    got = program(*args)
    inside = slot < cap
    for col, new, w in zip(state, got, words):
        want = np.asarray(col).copy()
        want[slot[inside]] = w[inside].astype(want.dtype)
        np.testing.assert_array_equal(np.asarray(new), want)


@pytest.mark.parametrize(
    "name", ["two_lanes_of_one_row", "every_lane_padding", "capacity_4"]
)
def test_clear_occupied_clears_bit_0_of_its_slots_alone(name, form):
    cap, live, width = SEAM_CASES[name]
    meta = random_state(cap).meta
    slot = padded(live, width, cap)
    program = jax.jit(fresh(bk._clear_occupied_impl))
    assert scatter_forms(program, meta, jnp.asarray(slot[::-1])) == {form}
    got = program(meta, jnp.asarray(slot[::-1]))
    want = np.asarray(meta).copy()
    want[slot[slot < cap]] &= ~1
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize(
    "rows,lanes,hinted",
    [
        # the benchmark's node and mesh: the pass is what a step cost
        (100_000_000, 64, False),
        (100_000_000, 1024, False),
        (100_000_000, 8192, False),
        (25_000_000, 1024, False),
        # ... a bulk load there is wide enough for the pass to pay
        (100_000_000, 16384, True),
        # round 2's tables, and every test's: the pass is 10-20 µs
        (2_000_000, 1024, True),
        (131_072, 64, True),
        (4, 64, True),
        # the edge: rows == 8192 lanes goes without
        (8192 * 64 - 1, 64, True),
        (8192 * 64, 64, False),
    ],
)
def test_the_scatter_is_hinted_where_a_pass_is_cheaper_than_the_loop(
    rows, lanes, hinted
):
    assert bk._scatter_hints(rows, lanes) == dict(
        indices_are_sorted=hinted, unique_indices=hinted
    )


# -- the step forms end to end, one form of the scatter against the other ---


def skewed_slots(rng, n: int, cap: int) -> np.ndarray:
    """Sequential interning under skew: most lanes in the low rows."""
    low = rng.choice(min(cap, 512), size=min(n * 2 // 3, 300), replace=False)
    rest = rng.choice(cap, size=n, replace=False)
    return np.unique(np.concatenate([low, rest]))[:n].astype(np.int32)


def packed_pin(rng, width, cap, n, now=NOW):
    s = skewed_slots(rng, n, cap)
    n = len(s)
    return bk.pack_batch_host(
        width, now, cap, s,
        rng.integers(0, 2, n), np.zeros(n, np.int64),
        rng.integers(0, 3, n), np.full(n, 100, np.int64),
        np.full(n, 60_000, np.int64), np.full(n, 100, np.int64),
        np.zeros(n, np.int64), np.zeros(n, np.int64),
    )


def uniform_pin(rng, width, cap, n, now=NOW):
    s = skewed_slots(rng, n, cap)
    return bk.pack_uniform_host(width, now, cap, s, 0, 0, 1, 100, 60_000, 100)


def collapsed_pin(rng, width, cap, n, now=NOW):
    s = skewed_slots(rng, max(1, n // 2), cap)
    counts = rng.integers(1, 3, len(s)).astype(np.int64)
    seg = np.repeat(np.arange(len(s), dtype=np.int32), counts)[:width]
    pos = np.concatenate([np.arange(c, dtype=np.int32) for c in counts])[:width]
    k = len(s)
    fields = (
        rng.integers(0, 2, k), np.zeros(k, np.int64), np.ones(k, np.int64),
        np.full(k, 100, np.int64), np.full(k, 60_000, np.int64),
        np.full(k, 100, np.int64), np.zeros(k, np.int64),
        np.zeros(k, np.int64),
    )
    return bk.pack_collapsed_host(width, now, cap, s, counts, fields, seg, pos)


def flat(core):
    """The mesh tier's flat form (parallel/sharded_engine.py
    `flat_*_fused`): the [n_shards, cap] state flattened inside the
    program, the slots global."""

    def run(state, pin):
        st, out = core(jax.tree.map(lambda x: x.reshape(-1), state), pin)
        return jax.tree.map(lambda x: x.reshape(2, -1), st), out

    return run


def scanned(core):
    return lambda state, pins: jax.lax.scan(fresh(core), state, pins)


# name -> (step form, pin builder, leading axes)
STEP_FORMS = {
    "fused_step": (bk._fused_step_core, packed_pin, ()),
    "uniform_step": (bk._uniform_step_core, uniform_pin, ()),
    "collapsed_step": (bk._collapsed_step_core, collapsed_pin, ()),
    "multi_fused_step": (scanned(bk._fused_step_core), packed_pin, (3,)),
    "multi_uniform_step": (scanned(bk._uniform_step_core), uniform_pin, (3,)),
    "flat_fused_step": (flat(bk._fused_step_core), packed_pin, ()),
    "flat_collapsed_step": (flat(bk._collapsed_step_core), collapsed_pin, ()),
    "vmapped_fused_step": (jax.vmap(bk._fused_step_core), packed_pin, (2,)),
    "vmapped_collapsed_step": (
        jax.vmap(bk._collapsed_step_core), collapsed_pin, (2,)
    ),
}


@pytest.mark.parametrize("width,n", [(64, 1), (64, 50), (1024, 900)])
@pytest.mark.parametrize("name", sorted(STEP_FORMS))
def test_step_form_with_the_loop_equals_the_same_with_the_pass(
    name, width, n, monkeypatch
):
    step, make_pin, lead = STEP_FORMS[name]
    cap = 128 * 24
    rng = np.random.default_rng(len(name) + width + n)
    pins = [make_pin(rng, width, cap, n, NOW + i) for i in range(max(lead + (1,)))]
    pin = jnp.asarray(np.stack(pins) if lead else pins[0])
    state = random_state(cap, seed=7)
    if name.startswith("vmapped"):
        state = jax.tree.map(lambda x: jnp.stack([x, x[::-1]]), state)
    if name.startswith("flat"):
        state = jax.tree.map(lambda x: x.reshape(2, -1), state)

    def run(form):
        monkeypatch.setattr(bk, "_SCATTER_PASS_ROWS_PER_LANE", FORMS[form])
        assert scatter_forms(jax.jit(fresh(step)), state, pin) == {form}
        return jax.jit(fresh(step))(state, pin)

    loop_state, loop_out = run("loop")
    pass_state, pass_out = run("pass")

    np.testing.assert_array_equal(np.asarray(loop_out), np.asarray(pass_out))
    for a, b in zip(loop_state, pass_state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- an edit cannot return to the pass at 100 M rows unnoticed ---------------

ONE_CHIP = {
    "fused_step": (bk.PACKED_IN_ROWS, ()),
    "multi_fused_step": (bk.PACKED_IN_ROWS, (2,)),
    "uniform_step": (bk.UNIFORM_IN_ROWS, ()),
    "multi_uniform_step": (bk.UNIFORM_IN_ROWS, (2,)),
    "collapsed_step": (bk.COLLAPSED_IN_ROWS, ()),
}


def step_shapes(name, cap, width, sharding=None):
    rows, lead = ONE_CHIP[name]
    state = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding
        ),
        jax.eval_shape(lambda: bk.make_state(cap)),
    )
    pin = jax.ShapeDtypeStruct(
        lead + (rows, width), jnp.int32, sharding=sharding
    )
    return state, pin


@pytest.mark.parametrize(
    "cap,width,hinted",
    [(100_000_000, 1024, False), (100_000_000, 64, False), (4096, 64, True)],
)
@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_lowered_step_scatters_into_the_table_in_the_form_the_rule_names(
    name, cap, width, hinted
):
    text = getattr(bk, name).lower(*step_shapes(name, cap, width)).as_text()
    scatters = re.findall(
        r'"stablehlo\.scatter"[^\n]*?indices_are_sorted = (\w+)[^\n]*?'
        r"unique_indices = (\w+)",
        text,
    )
    want = "true" if hinted else "false"
    assert len(scatters) == 12 and set(scatters) == {(want, want)}, scatters
    # the gathers are no pass in either form and keep their hints
    gathers = re.findall(
        r'"stablehlo\.gather"[^\n]*?indices_are_sorted = (\w+)'
        rf"[^\n]*?\(tensor<{cap}x",
        text,
    )
    assert len(gathers) == 12 and set(gathers) == {"true"}, gathers


# -- what the chip's compiler makes of it (no chip: a described v5e) ----------
#
# Nothing runs here; the compiler says what is compiled.  What the
# scatter costs is visible on the chip alone
# (scripts/probe_state_access.py).


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_on_a_v5e_the_step_updates_100m_rows_in_place_without_the_pass(
    name, one_chip
):
    """Lowered for a TPU, a 100 M-row table takes the row write: no XLA
    scatter into a column, one `row_write` kernel whose twelve results
    are the columns viewed as `[rows / 128, 128]`, written in place."""
    cap = 100_000_000
    compiled = (
        getattr(bk, name).lower(*step_shapes(name, cap, 64, one_chip)).compile()
    )
    lines = compiled.as_text().splitlines()
    assert not [l for l in lines if " scatter(" in l and f"[{cap}]" in l]
    kernels = [l for l in lines if "custom-call(" in l and "row_write" in l]
    assert len(kernels) == 1
    assert kernels[0].split(" custom-call(")[0].count(
        f"[{cap // 128},128]"
    ) == 12
    # in place: a cloned column would be 400 MB of temp
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


# -- the mesh engine's programs on the four chips of a described v5e-4 -------
#
# A [n_shards, cap] state gives a chip [1, cap], which the chip tiles
# T(1,128) where [cap] is T(1024): squeezing it for the one-chip body
# and expanding the result copied all twelve columns twice a step
# (twelve `reduce`, twelve `while`, a temp the size of the state; 20.9
# ms a dispatch at 25 M rows a shard — PERF.md §6, PR 31).  The state
# is flat columns on the keys axis so that there is nothing to copy.

SHARD_ROWS = 25_000_000
REC_DTYPES = dict(
    slot=jnp.int32, algo=jnp.int32, status=jnp.int32, limit=jnp.int64,
    remaining=jnp.int64, remf_hi=jnp.int32, remf_lo=jnp.uint32,
    duration=jnp.int64, t0=jnp.int64, expire_at=jnp.int64,
    burst=jnp.int64, invalid_at=jnp.int64,
)


@pytest.fixture(scope="module")
def mesh_engine(topo):
    """`ShardedDecisionEngine` as its constructor leaves it, less the
    state itself: nothing can be placed on a described device, so the
    programs are built and lowered for shapes."""
    from gubernator_tpu.parallel.mesh import keys_sharding, make_mesh
    from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine

    engine = object.__new__(ShardedDecisionEngine)
    engine.mesh = make_mesh(topo.devices)
    engine.n_shards = len(topo.devices)
    engine._single_program = False
    engine.shard_capacity = SHARD_ROWS
    engine.capacity = SHARD_ROWS * engine.n_shards
    engine.max_kernel_width = 8192
    engine._placement = keys_sharding(engine.mesh)
    engine._build_step()
    return engine


def mesh_program_args(engine, name, width):
    """Static arguments and shapes, placed as the engine places
    them, for one of its programs."""
    from jax.sharding import NamedSharding, PartitionSpec

    n_sh = engine.n_shards
    keys = engine._placement
    everywhere = NamedSharding(engine.mesh, PartitionSpec())

    def sds(shape, dtype=jnp.int32, sharding=keys):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = engine._state_shapes()
    window = min(engine.shard_capacity, engine.SWEEP_WINDOW)
    start = sds((), jnp.int32, everywhere)
    static = {"window": window} if name == "_sweep_scan" else {}
    return static, {
        "_collapsed_fused": (state, sds((n_sh, bk.COLLAPSED_IN_ROWS, width))),
        "_packed_fused": (state, sds((n_sh, bk.PACKED_IN_ROWS, width))),
        "_load_step": (
            state,
            bk.SlotRecord(
                **{f: sds((n_sh, width), dt) for f, dt in REC_DTYPES.items()}
            ),
        ),
        "_clear_step": (state.meta, sds((n_sh, width))),
        "_sweep_scan": (
            state.meta, state.hi2, state.expire_lo,
            sds((), jnp.int32, everywhere), sds((), jnp.uint32, everywhere),
            start,
        ),
        "_sweep_commit": (state.meta, sds((n_sh * window,)), start),
    }[name]


# program -> (lanes, scatters into a shard's 25 M-row column)
MESH_PROGRAMS = {
    "_collapsed_fused": (256, 12),
    "_packed_fused": (256, 12),
    "_load_step": (64, 12),
    "_clear_step": (64, 1),
    "_sweep_scan": (0, 0),
    "_sweep_commit": (0, 0),
}


@pytest.mark.parametrize("name", sorted(MESH_PROGRAMS))
def test_on_a_v5e_4_the_mesh_program_touches_a_shards_columns_in_place(
    name, mesh_engine
):
    width, n_scatters = MESH_PROGRAMS[name]
    static, shapes = mesh_program_args(mesh_engine, name, width)
    compiled = getattr(mesh_engine, name).lower(*shapes, **static).compile()
    # per device: a cloned column would be 100 MB, the state 1.2 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    lines = compiled.as_text().splitlines()
    column = f"[{SHARD_ROWS}]"
    # a chip's view of a column is the column: no [1, cap] block, so
    # no `reduce` / `copy` out of one and no `while` copying back
    assert not [l for l in lines if f"[1,{SHARD_ROWS}]" in l]
    assert not [l for l in lines if " while(" in l and column in l]
    assert not [l for l in lines if " reduce(" in l and column in l]
    scatters = [l for l in lines if " scatter(" in l and column in l]
    assert len(scatters) == n_scatters
    for line in scatters:
        assert "indices_are_sorted=true" not in line
        assert "unique_indices=true" not in line


@pytest.mark.parametrize("name,width", [
    ("_clear_step", 64), ("_clear_step", 1024), ("_load_step", 1 << 20)])
def test_on_a_v5e_4_the_clear_and_the_restore_write_over_what_they_were_given(
    name, width, mesh_engine
):
    """Donated (PR 34 for the clear): a round with evictions rewrites a
    shard's meta words where they lie, and the restore's piece — 2^20
    lanes a shard, what a 100 M-row restore scatters — every column:
    the output is the input's buffer, no copy of a column is made."""
    static, shapes = mesh_program_args(mesh_engine, name, width)
    compiled = getattr(mesh_engine, name).lower(*shapes, **static).compile()
    stats = compiled.memory_analysis()
    # (a tuple's output adds its table of pointers, not a column)
    assert 0 <= stats.output_size_in_bytes - stats.alias_size_in_bytes < 4096
    assert stats.alias_size_in_bytes >= 4 * SHARD_ROWS
    assert stats.temp_size_in_bytes < 64 << 20
    assert "input_output_alias={ {" in compiled.as_text()


def test_on_a_v5e_4_the_probe_reads_the_program_that_serves(mesh_engine):
    """The start-up probe compiles the engine's own two step programs
    at their shardings, against what one chip holds of the state.  The
    one-chip program at shard capacity — what the probe used to ask —
    says yes whatever the mesh program does: with the step the engine
    served before (state [n_shards, cap], squeezed and expanded around
    the body) in its place, the probe has to say no."""
    from gubernator_tpu.parallel.mesh import KEYS_AXIS, shard_map

    state = mesh_engine._state_shapes()
    shard_bytes = 48 * SHARD_ROWS
    pins = {
        "_collapsed_fused": bk.COLLAPSED_IN_ROWS,
        "_packed_fused": bk.PACKED_IN_ROWS,
    }
    temps = [
        bk.compiled_temp_bytes(
            getattr(mesh_engine, name),
            state,
            jax.ShapeDtypeStruct(
                (mesh_engine.n_shards, rows, 64), jnp.int32,
                sharding=mesh_engine._placement,
            ),
        )
        for name, rows in pins.items()
    ]
    yes = mesh_engine._mesh_step_ok()
    assert yes == bk.in_place_verdict(max(temps), shard_bytes)
    assert yes.ok and f"bound {shard_bytes // 4} B" in yes.reason
    one_chip_temp = bk.compiled_temp_bytes(
        bk.fused_step, *step_shapes("fused_step", SHARD_ROWS, 64)
    )
    assert one_chip_temp not in temps

    spec = jax.sharding.PartitionSpec(KEYS_AXIS)
    specs = jax.tree.map(lambda _: spec, bk.make_state(0))

    def before(core):
        def squeezed_and_expanded(state, pin):
            new, pout = core(jax.tree.map(lambda x: x[0], state), pin[0])
            return jax.tree.map(lambda x: x[None], new), pout[None]

        return jax.jit(
            shard_map(
                squeezed_and_expanded,
                mesh=mesh_engine.mesh,
                in_specs=(specs, spec),
                out_specs=(specs, spec),
            ),
            donate_argnums=(0,),
        )

    served = dict(vars(mesh_engine))
    try:
        mesh_engine._packed_fused = before(bk._fused_step_core)
        mesh_engine._collapsed_fused = before(bk._collapsed_step_core)
        mesh_engine._state_shapes = lambda: jax.tree.map(
            lambda c: jax.ShapeDtypeStruct(
                (mesh_engine.n_shards, SHARD_ROWS), c.dtype,
                sharding=c.sharding,
            ),
            state,
        )
        no = mesh_engine._mesh_step_ok()
    finally:
        vars(mesh_engine).clear()
        vars(mesh_engine).update(served)
    assert not no.ok and f">= bound {shard_bytes // 4} B" in no.reason
    assert int(no.reason.split()[1]) > shard_bytes
