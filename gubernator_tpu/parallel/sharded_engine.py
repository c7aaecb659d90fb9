"""ShardedDecisionEngine — bucket state sharded over a device mesh.

The multi-chip execution engine: every state column is ONE 1-D array
of n_shards × shard_capacity rows sharded over the "keys" mesh axis,
shard s owning rows [s·cap, (s+1)·cap) — so a chip's view under
shard_map is the [cap] column the one-chip step programs
(ops/bucket_kernel.py) take as it is.  (A [n_shards, cap] array would
give a chip [1, cap], which does not share a layout with [cap] on a
TPU: squeezing and re-expanding it around the body copied all twelve
columns twice a step, O(rows) whatever the batch — PERF.md §6, PR 31.)
Slots stay shard-local.  Each request batch is routed host-side to its
owning shard (fnv1a(key) mod n_shards — the TPU-native replacement for
the worker hash ring, reference: gubernator_pool.go:183-187) and
applied by ONE jitted shard_map step: every chip gathers/updates only
its local state block, so the decision path needs zero inter-chip
traffic (PERF.md §7 — the measured argument for why zero-ICI is the
optimum here); the packed per-shard outputs return in the response
readback, so cluster metrics cost no extra transfer.

Per-key serialization and eviction-clear scheduling reuse the round
scheme of the single-device engine (core/engine.py), applied per shard.
"""

from __future__ import annotations

import threading
import time as _time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

from gubernator_tpu.clock import SYSTEM_CLOCK, Clock
from gubernator_tpu.gregorian import (
    GregorianError,
    dt_from_ms,
    gregorian_duration,
    gregorian_expiration,
)
from gubernator_tpu.hashing import fnv1a_64, fnv1a_64_batch, pack_keys
from gubernator_tpu.ops.bucket_kernel import (
    COLLAPSED_IN_ROWS,
    PACKED_IN_ROWS,
    BucketState,
    ProbeVerdict,
    compiled_temp_bytes,
    first_line,
    in_place_verdict,
    make_state,
)
from gubernator_tpu.core.engine import require_in_place
from gubernator_tpu.core.native import make_intern_table
from gubernator_tpu.parallel.mesh import (
    KEYS_AXIS,
    keys_sharding,
    make_mesh,
    shard_map as _shard_map,
)
from gubernator_tpu.types import Behavior, RateLimitReq, RateLimitResp, Status
from gubernator_tpu.utils.metrics import (
    CPU_MIN_ITEMS,
    CPU_STAGES,
    DurationStat,
    engine_stages,
    stage,
)
from gubernator_tpu.utils.tracing import span

_I32 = np.int32
_I64 = np.int64

# Hot-loop int constants (IntFlag/IntEnum ops are ~1.5µs each in
# CPython; see core/engine.py note).
_GREG = int(Behavior.DURATION_IS_GREGORIAN)
_OVER_I = int(Status.OVER_LIMIT)
_STATUS_OF = {int(st): st for st in Status}


def _pad_size(n: int, floor: int = 64) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


class ShardedDecisionEngine:
    """Decision engine over an N-device mesh (total capacity =
    n_shards × shard_capacity)."""

    def __init__(
        self,
        shard_capacity: int = 50_000,
        *,
        mesh: Optional[Mesh] = None,
        clock: Clock = SYSTEM_CLOCK,
        max_kernel_width: int = 8192,
        store=None,  # gubernator_tpu.store.Store (write-through hooks)
        single_program: Optional[bool] = None,
    ):
        if not jax.config.jax_enable_x64:
            raise RuntimeError("gubernator_tpu requires jax x64")
        import os as _os

        from gubernator_tpu.platform_guard import disable_cpu_persistent_cache

        disable_cpu_persistent_cache()
        self.store = store
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_shards = self.mesh.shape[KEYS_AXIS]
        # Execution strategy.  shard_map (default) places one state
        # block per mesh device — the real multi-chip path.  The
        # single-program mode runs the SAME per-shard semantics as one
        # vmapped XLA program on one device: on a one-core host (or a
        # one-chip backend serving a sharded keyspace) the per-device
        # program dispatch of an N-wide virtual mesh is pure overhead
        # (measured: 1.68ms -> 3.78ms per identical 2048-item batch
        # going 1 -> 8 virtual CPU devices).  Semantics equivalence is
        # pinned by tests/test_multi_schedule.py.
        if single_program is None:
            single_program = (
                _os.environ.get("GUBER_SHARDS_SINGLE_PROGRAM", "0") == "1"
            )
        self._single_program = bool(single_program)
        self.shard_capacity = shard_capacity
        self.capacity = shard_capacity * self.n_shards
        self.clock = clock
        self.max_kernel_width = max_kernel_width
        # Native C++ tables when buildable (batch schedule fast path).
        self.tables = [
            make_intern_table(shard_capacity) for _ in range(self.n_shards)
        ]
        # All-native tables unlock the single-FFI host tier
        # (git_multi_schedule: routing + interning + rounds + TTL +
        # dispatch order in one call — VERDICT r4 weak #3).
        from gubernator_tpu.core.native import NativeInternTable

        self._multi_ok = all(
            isinstance(t, NativeInternTable) for t in self.tables
        )
        self._lock = threading.Lock()
        self._sweep_cursor = 0  # next window start for incremental sweep
        self.requests_total = 0
        self.over_limit_total = 0
        self.batches_total = 0
        self.rounds_total = 0
        # Decision-plane device dispatch counter (see DecisionEngine).
        self.dispatches_total = 0
        self.rows_loaded_total = 0  # rows restored through load()
        # GLOBAL column merge as a psum over the mesh (ROADMAP item 1 /
        # PERF.md §24): a whole-batch round's per-shard packed outputs
        # are scattered to their request positions ON DEVICE and
        # `lax.psum`'d across the keys axis, so the host reads ONE
        # request-ordered [PACKED_OUT_ROWS, n] buffer instead of
        # unpermuting n_shards row sets — this is the ICI-level
        # aggregation the GLOBAL broadcast's owner re-read rides
        # (cluster/global_manager.py).  GUBER_PSUM_MERGE=0 disables.
        self._use_psum_merge = (
            not self._single_program
            and self.n_shards > 1
            and _os.environ.get("GUBER_PSUM_MERGE", "1") != "0"
        )
        self._merge_progs: Dict[Tuple[int, int], object] = {}
        # device.step: the host's enqueue wall of one mesh dispatch.
        self.round_duration = DurationStat()
        # The served path's stages — core/engine.py's vocabulary, plus
        # the router that exists only here and the columnar restore's
        # pieces (`_load_columns`), as DecisionEngine has them.
        self.stages = engine_stages(extra=("mesh.route", "engine.load"))
        # True while the lock's holder serves a section that reads its
        # CPU clock (apply_columnar); only CPU_STAGES, all of them under
        # the lock, act on it.
        self._cpu_section = False  # guberlint: guarded-by _lock
        # Rows of one columnar restore piece, and the lanes of its
        # scatter on every shard: as wide as a shard up to 2^20, so
        # that a restore compiles one `_load_step` (DecisionEngine's
        # `load_width`, per shard).
        self.load_width = min(1 << 20, _pad_size(shard_capacity, floor=1024))
        # Shared d2h transfer batching across concurrent callers
        # (core/readback.py — the mesh outputs combine the same way).
        from gubernator_tpu.core.readback import ReadbackCombiner

        self.readback = ReadbackCombiner()

        # Where an array with the shard axis leading lives: one block
        # per mesh device (shard_map), or everything on the first
        # device with the vmapped step keeping per-shard isolation
        # inside one XLA program (single-program).  The state is
        # allocated THERE — each shard's rows on its own device, never
        # staged whole through one chip — and every round's packed
        # input is placed the same way (`_put`), host → owning device.
        self._placement = (
            SingleDeviceSharding(next(iter(self.mesh.devices.flat)))
            if self._single_program
            else keys_sharding(self.mesh)
        )
        # Every field gets its own buffer (the step donates the state):
        # one flat column of n_shards × shard_capacity rows (module
        # docstring), in both modes.
        self._state: BucketState = jax.tree.map(
            lambda sds: jnp.zeros(
                sds.shape, sds.dtype, device=self._placement
            ),
            self._state_shapes(),
        )
        self._build_step()
        # The probe compiles what is served: this engine's own step
        # programs at their real shardings.  On an accelerator a no
        # refuses the start (core/engine.py `require_in_place`).
        self.probes: dict = {
            "mesh_step": require_in_place(self._mesh_step_ok(), "mesh_step")
        }

    # ------------------------------------------------------------------

    def _state_shapes(self) -> BucketState:
        """The state as shapes placed where the state lives."""
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                (self.capacity,), leaf.dtype, sharding=self._placement
            ),
            make_state(0),
        )

    def _mesh_step_ok(self, width: int = 64) -> ProbeVerdict:
        """`fused_step_ok` for this engine: compile its two donated
        step programs as they are served and read the compiler's
        memory analysis, which is per device — so the bound comes from
        what one device holds of the state.  The worse of the two is
        the verdict.  (The one-chip program at shard capacity says
        nothing here: it compiled in place while the `[1, cap]` blocks
        around it cloned the state every step.)"""
        state = self._state_shapes()
        per_device = sum(
            int(np.prod(c.sharding.shard_shape(c.shape))) * c.dtype.itemsize
            for c in state
        )
        try:
            temp = max(
                compiled_temp_bytes(
                    program,
                    state,
                    jax.ShapeDtypeStruct(
                        (self.n_shards, rows, width), jnp.int32,
                        sharding=self._placement,
                    ),
                )
                for program, rows in (
                    (self._collapsed_fused, COLLAPSED_IN_ROWS),
                    (self._packed_fused, PACKED_IN_ROWS),
                )
            )
        except Exception as e:  # noqa: BLE001 — the refusal is the verdict
            return ProbeVerdict(False, first_line(e))
        return in_place_verdict(temp, per_device)

    def _build_step(self):
        mesh = self.mesh
        pspec = P(KEYS_AXIS)

        if self._single_program:
            self._build_step_single_program()
            return

        from gubernator_tpu.ops.bucket_kernel import (
            SlotRecord,
            _clear_occupied_impl,
            _collapsed_values,
            _fused_step_core,
            _load_slots_impl,
            _scatter_values,
        )
        from gubernator_tpu.ops.expiry import (
            sweep_window_commit,
            sweep_window_scan,
        )

        # Inside shard_map a state column is the shard's own [cap]
        # rows; what the host packs per shard (slots, packed inputs,
        # restore records) carries the shard axis and arrives [1, ...].
        def local_clear(occupied, slots):
            return _clear_occupied_impl(occupied, slots[0])

        # Donated, as every program on the state is: a round with
        # evictions rewrites a shard's meta words in place instead of
        # copying its 100 MB column (25 M rows a chip).
        self._clear_step = jax.jit(
            _shard_map(
                local_clear,
                mesh=mesh,
                in_specs=(pspec, pspec),
                out_specs=pspec,
            ),
            donate_argnums=(0,),
        )

        # Packed columnar mesh step (see bucket_kernel PACKED_IN_ROWS):
        # the whole round crosses the host↔device boundary as ONE
        # int32 [n_shards, 16, width] buffer in and ONE
        # [n_shards, 5, width] buffer out — on a dispatch-bound backend
        # transfer count, not bytes, is what the step pays for.
        def local_packed_fused(state, pin):
            new_state, pout = _fused_step_core(state, pin[0])
            return new_state, pout[None]

        # Collapsed duplicate-segment step per shard (hot keys — see
        # bucket_kernel COLLAPSED_IN_ROWS; the single-device engine's
        # closed form, run under shard_map).
        def local_collapsed_fused(state, pin):
            slot, vals2, pout = _collapsed_values(state, pin[0])
            return _scatter_values(state, slot, vals2), pout[None]

        state_specs2 = jax.tree.map(lambda _: pspec, make_state(0))
        self._packed_fused = jax.jit(
            _shard_map(
                local_packed_fused,
                mesh=mesh,
                in_specs=(state_specs2, pspec),
                out_specs=(state_specs2, pspec),
            ),
            donate_argnums=(0,),
        )
        self._collapsed_fused = jax.jit(
            _shard_map(
                local_collapsed_fused,
                mesh=mesh,
                in_specs=(state_specs2, pspec),
                out_specs=(state_specs2, pspec),
            ),
            donate_argnums=(0,),
        )

        # Store read-through hydration: sharded counterpart of
        # core.engine load_slots (one batched scatter per round).
        def local_load(state, rec):
            return _load_slots_impl(state, SlotRecord(*(x[0] for x in rec)))

        rec_specs = jax.tree.map(
            lambda _: pspec, SlotRecord(*(0,) * len(SlotRecord._fields))
        )
        self._load_step = jax.jit(
            _shard_map(
                local_load,
                mesh=mesh,
                in_specs=(state_specs2, rec_specs),
                out_specs=state_specs2,
            ),
            donate_argnums=(0,),
        )

        # The windowed sweep's two programs (ops/expiry.py), a shard's
        # window at the same shard-local start on every chip: the
        # window's meta words stay on their chips for the commit,
        # `order` / `count` come back with the shard axis leading.
        # guberlint: shapes columns [n_sh * cap] fixed; window static (SWEEP_WINDOW)
        @partial(jax.jit, static_argnames=("window",))
        def sharded_sweep_scan(*args, window):
            def local(*shard_args):
                meta_w, order, count = sweep_window_scan(
                    *shard_args, window=window
                )
                return meta_w, order[None], count[None]

            return _shard_map(
                local,
                mesh=mesh,
                in_specs=(pspec, pspec, pspec, P(), P(), P()),
                out_specs=(pspec, pspec, pspec),
            )(*args)

        self._sweep_scan = sharded_sweep_scan
        self._sweep_commit = jax.jit(
            _shard_map(
                sweep_window_commit,
                mesh=mesh,
                in_specs=(pspec, pspec, P()),
                out_specs=pspec,
            ),
            donate_argnums=(0,),
        )
        self._flat_ok = False  # flat dispatch is single-program-only

    def _build_step_single_program(self):
        """One vmapped XLA program over the shard axis instead of one
        shard_map program per mesh device — the same per-shard
        gather/update/scatter semantics with zero per-device dispatch
        overhead (see __init__).  The state is the same flat columns;
        the vmapped programs see them as [n_shards, cap] inside their
        jit, the flat executors take them as they are."""
        from gubernator_tpu.ops.bucket_kernel import (
            _clear_occupied_impl,
            _collapsed_step_core,
            _collapsed_values,
            _fused_step_core,
            _load_slots_impl,
            _scatter_values,
        )
        from gubernator_tpu.ops.expiry import (
            sweep_window_commit,
            sweep_window_scan,
        )

        n_sh, cap = self.n_shards, self.shard_capacity

        def by_shard(tree):
            return jax.tree.map(lambda x: x.reshape(n_sh, cap), tree)

        def flat(tree):
            return jax.tree.map(lambda x: x.reshape(-1), tree)

        def vmapped_clear(meta, slots):
            return flat(jax.vmap(_clear_occupied_impl)(by_shard(meta), slots))

        # The one-device programs themselves, vmapped: they keep the
        # module names the benchmark's step patterns know
        # (tests/test_step_names.py).
        def vmapped_fused_step_core(state, pin):
            st, pout = jax.vmap(_fused_step_core)(by_shard(state), pin)
            return flat(st), pout

        def vmapped_collapsed_step_core(state, pin):
            st, pout = jax.vmap(_collapsed_step_core)(by_shard(state), pin)
            return flat(st), pout

        def vmapped_load(state, rec):
            return flat(jax.vmap(_load_slots_impl)(by_shard(state), rec))

        # guberlint: shapes meta [n_sh * cap] fixed; slots [n_sh, C], C on the clear ladder
        self._clear_step = jax.jit(vmapped_clear, donate_argnums=(0,))
        # guberlint: shapes pin [n_sh, PACKED_IN_ROWS, W], W on the width ladder; state [n_sh * cap] fixed
        self._packed_fused = jax.jit(
            vmapped_fused_step_core, donate_argnums=(0,)
        )
        # guberlint: shapes pin [n_sh, COLLAPSED_IN_ROWS, W], W on the width ladder; state [n_sh * cap] fixed
        self._collapsed_fused = jax.jit(
            vmapped_collapsed_step_core, donate_argnums=(0,)
        )
        # guberlint: shapes rec [n_sh, R], R on the restore ladder; state [n_sh * cap] fixed
        self._load_step = jax.jit(vmapped_load, donate_argnums=(0,))

        # The sweep's programs slice the last axis of [n_shards, cap].
        # guberlint: shapes columns [n_sh * cap] fixed; window static (SWEEP_WINDOW)
        @partial(jax.jit, static_argnames=("window",))
        def vmapped_sweep_scan(meta, hi2, expire_lo, *scalars, window):
            return sweep_window_scan(
                *by_shard((meta, hi2, expire_lo)), *scalars, window=window
            )

        self._sweep_scan = vmapped_sweep_scan

        def vmapped_sweep_commit(meta, meta_window, start):
            return flat(
                sweep_window_commit(by_shard(meta), meta_window, start)
            )

        # guberlint: shapes meta [n_sh * cap], meta_window [n_sh, window] fixed per capacity
        self._sweep_commit = jax.jit(
            vmapped_sweep_commit, donate_argnums=(0,)
        )

        # Flat executors: the hot columnar path globalizes slots
        # (shard*cap + slot) and runs the WHOLE batch as one
        # non-batched program over the flat state as it is — no
        # per-shard padded blocks at all.
        # pack_batch_host padding lanes run up to capacity + width;
        # the int32 slot row caps the flat layout at 2^31.
        self._flat_ok = (
            self.capacity + 2 * self.max_kernel_width < 2**31
        )

        def flat_packed_fused(state, pin):
            st, pout = _fused_step_core(state, pin[0])
            return st, pout[None]

        def flat_collapsed_fused(state, pin):
            slot, vals2, pout = _collapsed_values(state, pin[0])
            return _scatter_values(state, slot, vals2), pout[None]

        # guberlint: shapes pin [1, PACKED_IN_ROWS, W] per shard, W on the width ladder; state [n_sh * cap] fixed
        self._flat_fused = jax.jit(flat_packed_fused, donate_argnums=(0,))
        # guberlint: shapes pin [1, COLLAPSED_IN_ROWS, W] on the width ladder; state [n_sh * cap] fixed
        self._flat_collapsed_fused = jax.jit(
            flat_collapsed_fused, donate_argnums=(0,)
        )

    # ------------------------------------------------------------------

    def shard_of(self, key: str) -> int:
        return fnv1a_64(key.encode()) % self.n_shards

    def _stage(self, name: str, work: bool = True) -> stage:
        return stage(
            name, self.stages[name], work,
            # guberlint: ok lock — read for CPU_STAGES only, each of
            # them entered under _lock
            cpu=name in CPU_STAGES and self._cpu_section,
        )

    def _host_state(self) -> BucketState:
        """The state's columns on the host, seen [n_shards,
        shard_capacity] (a reshape of the flat column, free in numpy):
        what `load`, `export_items` and `save` index by (shard, slot).
        Back on the device goes `column.reshape(-1)` through `_put`."""
        shape = (self.n_shards, self.shard_capacity)
        return jax.tree.map(
            lambda x: np.asarray(x).reshape(shape), self._state
        )

    def _put(self, host: np.ndarray) -> jax.Array:
        """One host buffer whose leading axis divides by shard (a
        [n_shards, ...] input, or a flat state column) → device, each
        shard's block straight to the device that owns it."""
        # guberlint: ok drift — sharded twin of engine.py's device.h2d site
        with self._stage("device.h2d"):
            return jax.device_put(host, self._placement)

    def _step(self, program, pin):
        """One round's donated mesh step program as ONE device.launch:
        the jitted call returning (the enqueue) and the donated
        state's old buffers let go.  Returns the packed output."""
        # guberlint: ok drift — sharded twin of engine.py's device.launch site
        with self._stage("device.launch"):
            self._state, pout = program(self._state, pin)
            self.dispatches_total += 1
        return pout

    def _apply_shard_clears(self, clears: List[List[int]]) -> None:
        """Eviction clears, one padded [n_shards, csize] scatter.
        `clears[sh]` lists slots to scrub on shard sh."""
        n_clear = max((len(c) for c in clears), default=0)
        if not n_clear:
            return
        cap = self.shard_capacity
        # guberlint: ok drift — sharded twin of engine.py's engine.evict_clear site
        with self._stage("engine.evict_clear"):
            csize = _pad_size(n_clear, floor=16)
            c = np.tile(
                np.arange(cap, cap + csize, dtype=_I64).astype(_I32),
                (self.n_shards, 1),
            )
            for sh in range(self.n_shards):
                c[sh, : len(clears[sh])] = clears[sh]
            self._state = self._state._replace(
                meta=self._clear_step(
                    self._state.meta, jax.device_put(c, self._placement)
                )
            )
            self.dispatches_total += 1

    def _apply_shard_restores(self, restores: List[List[tuple]]) -> None:
        """Hydrate store-provided bucket values into fresh slots on
        every shard: ONE sharded load scatter (padded to the widest
        shard's restore count).  reference: algorithms.go:46-54."""
        from gubernator_tpu.core.engine import build_restore_record
        from gubernator_tpu.ops.bucket_kernel import SlotRecord

        n_sh = self.n_shards
        cap = self.shard_capacity
        size = _pad_size(max(len(r) for r in restores), floor=16)
        cols: Dict[str, List[np.ndarray]] = {}
        for sh in range(n_sh):
            rec = build_restore_record(restores[sh], cap, size=size)
            for name, arr in rec.items():
                cols.setdefault(name, []).append(arr)
        rec_stacked = SlotRecord(
            **{
                name: self._put(np.stack(arrs))
                for name, arrs in cols.items()
            }
        )
        with self._stage("device.launch"):
            self._state = self._load_step(self._state, rec_stacked)
            self.dispatches_total += 1

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        if now_ms is None:
            now_ms = self.clock.now_ms()
        n = len(requests)
        if n == 0:
            return []
        responses: List[Optional[RateLimitResp]] = [None] * n
        now_dt = None

        greg_dur = np.zeros(n, dtype=_I64)
        greg_exp = np.zeros(n, dtype=_I64)
        valid: List[int] = []
        for i, r in enumerate(requests):
            if int(r.behavior) & _GREG:
                if now_dt is None:
                    # Same time-source invariant as core.engine: civil
                    # time derives from now_ms, never a second read.
                    now_dt = dt_from_ms(now_ms)
                try:
                    greg_dur[i] = gregorian_duration(now_dt, r.duration)
                    greg_exp[i] = gregorian_expiration(now_dt, r.duration)
                except GregorianError as e:
                    responses[i] = RateLimitResp(error=str(e))
                    continue
            valid.append(i)

        with self._lock:
            self._apply(requests, valid, greg_dur, greg_exp, now_ms, responses)
            self.requests_total += n
            self.batches_total += 1
        return responses  # type: ignore[return-value]

    def _apply(
        self,
        requests: Sequence[RateLimitReq],
        valid: List[int],
        greg_dur: np.ndarray,
        greg_exp: np.ndarray,
        now_ms: int,
        responses: List[Optional[RateLimitResp]],
    ) -> None:
        if not valid:
            return
        n_sh = self.n_shards
        # Route + intern + schedule rounds (per shard).
        seqs: List[Dict[int, int]] = [dict() for _ in range(n_sh)]
        rounds: Dict[int, List[List[Tuple[int, int]]]] = {}
        clear_rounds: Dict[int, List[List[int]]] = {}
        restore_rounds: Dict[int, List[List[tuple]]] = {}
        slot_of: Dict[int, Tuple[int, int]] = {}
        for i in valid:
            key = requests[i].hash_key()
            sh = self.shard_of(key)
            is_new = self.store is not None and not self.tables[sh].contains(key)
            evicted: List[int] = []
            slot = self.tables[sh].intern(key, now_ms, evicted)
            for es in evicted:
                k = seqs[sh].get(es, 0)
                clear_rounds.setdefault(k, [[] for _ in range(n_sh)])[sh].append(es)
            k = seqs[sh].get(slot, 0)
            seqs[sh][slot] = k + 1
            rounds.setdefault(k, [[] for _ in range(n_sh)])[sh].append((i, slot))
            slot_of[i] = (sh, slot)
            if is_new:
                # Read-through (reference: algorithms.go:46-54).
                item = self.store.get(requests[i])
                if item is not None and item.value is not None:
                    restore_rounds.setdefault(k, [[] for _ in range(n_sh)])[
                        sh
                    ].append((slot, item))

        expire_of: Dict[int, int] = {}
        # guberlint: ok drift — sharded twin of engine.py's
        # engine.batch site; same stage name keeps the tracing
        # oracle backend-agnostic (tests/test_tracing.py)
        with span("engine.batch", batch=len(valid), rounds=len(rounds)):
            if (
                self.store is None
                and len(rounds) > 1
                and self._collapse_dataclass_sharded(
                    requests, valid, rounds, clear_rounds,
                    greg_dur, greg_exp, now_ms, responses,
                )
            ):
                return
            for k in sorted(set(rounds) | set(clear_rounds)):
                members = rounds.get(k, [[] for _ in range(n_sh)])
                clears = clear_rounds.get(k, [[] for _ in range(n_sh)])
                restores = restore_rounds.get(k)
                # Chunk wide rounds to bound compiled shapes.
                offset = 0
                while True:
                    chunk = [m[offset : offset + self.max_kernel_width] for m in members]
                    if not any(chunk) and offset > 0:
                        break
                    # guberlint: ok drift — sharded twin of
                    # engine.py's engine.round site
                    with span(
                        "engine.round",
                        round=k,
                        width=max(len(c) for c in chunk),
                    ):
                        self._run_round(
                            chunk,
                            clears if offset == 0 else [[] for _ in range(n_sh)],
                            greg_dur,
                            greg_exp,
                            now_ms,
                            requests,
                            responses,
                            restores=restores if offset == 0 else None,
                            expire_of=expire_of,
                        )
                    self.rounds_total += 1
                    offset += self.max_kernel_width
                    if all(offset >= len(m) for m in members):
                        break

        if self.store is not None:
            from gubernator_tpu.core.engine import write_through_store

            write_through_store(
                self.store, requests, valid, greg_dur, now_ms, responses,
                expire_of,
            )

    def _run_round(
        self,
        members: List[List[Tuple[int, int]]],
        clears: List[List[int]],
        greg_dur: np.ndarray,
        greg_exp: np.ndarray,
        now_ms: int,
        requests: Sequence[RateLimitReq],
        responses: List[Optional[RateLimitResp]],
        restores: Optional[List[List[tuple]]] = None,
        expire_of: Optional[Dict[int, int]] = None,
    ) -> None:
        from gubernator_tpu.ops.bucket_kernel import (
            PACKED_IN_ROWS,
            pack_batch_host,
            unpack_out_host,
        )

        n_sh = self.n_shards
        cap = self.shard_capacity
        width = _pad_size(max((len(m) for m in members), default=1))

        # Eviction clears run as a separate sharded scatter (own shape
        # ladder, independent of the apply step's batch width).
        self._apply_shard_clears(clears)
        if restores is not None and any(restores):
            self._apply_shard_restores(restores)

        # One packed [n_sh, 16, width] buffer, host-presorted per shard
        # -- the same 3-op program as the columnar path (PERF.md sec 4);
        # the old per-column transfers paid the per-op dispatch floor
        # 10x per round.
        buf = np.zeros((n_sh, PACKED_IN_ROWS, width), dtype=_I32)
        order_of: List[np.ndarray] = []
        limits_of: List[np.ndarray] = []
        host_expire: List[Tuple[List[int], List[int]]] = [
            ([], []) for _ in range(n_sh)
        ]  # per shard: (slots, expires)
        empty64 = np.empty(0, dtype=_I64)
        for sh in range(n_sh):
            m = len(members[sh])
            if m == 0:
                pack_batch_host(
                    width, now_ms, cap, np.empty(0, dtype=_I32),
                    empty64, empty64, empty64, empty64, empty64, empty64,
                    empty64, empty64, out=buf[sh],
                )
                order_of.append(np.empty(0, dtype=np.int64))
                limits_of.append(empty64)
                continue
            c_slot = np.empty(m, dtype=_I32)
            c_algo = np.empty(m, dtype=_I32)
            c_beh = np.empty(m, dtype=_I32)
            c_hits = np.empty(m, dtype=_I64)
            c_limit = np.empty(m, dtype=_I64)
            c_dur = np.empty(m, dtype=_I64)
            c_burst = np.empty(m, dtype=_I64)
            c_gdur = np.empty(m, dtype=_I64)
            c_gexp = np.empty(m, dtype=_I64)
            for lane, (i, slot) in enumerate(members[sh]):
                r = requests[i]
                c_slot[lane] = slot
                c_algo[lane] = int(r.algorithm)
                beh = int(r.behavior)
                c_beh[lane] = beh
                c_hits[lane] = r.hits
                c_limit[lane] = r.limit
                c_dur[lane] = r.duration
                c_burst[lane] = r.burst
                c_gdur[lane] = greg_dur[i]
                c_gexp[lane] = greg_exp[i]
                exp = (
                    greg_exp[i]
                    if beh & _GREG
                    else now_ms + r.duration
                )
                host_expire[sh][0].append(slot)
                host_expire[sh][1].append(exp)
                if expire_of is not None:
                    expire_of[i] = int(exp)
            sort_idx = np.argsort(c_slot, kind="stable")
            pack_batch_host(
                width, now_ms, cap,
                np.ascontiguousarray(c_slot[sort_idx]),
                c_algo[sort_idx], c_beh[sort_idx], c_hits[sort_idx],
                c_limit[sort_idx], c_dur[sort_idx], c_burst[sort_idx],
                c_gdur[sort_idx], c_gexp[sort_idx],
                out=buf[sh],
            )
            order_of.append(sort_idx)
            limits_of.append(c_limit)

        t0 = _time.monotonic()
        pout = self._step(self._packed_fused, self._put(buf))
        self.round_duration.observe(_time.monotonic() - t0)

        arr = self.readback.register(pout).fetch()
        for sh in range(n_sh):
            mm = len(members[sh])
            if mm == 0:
                continue
            o_status, o_rem, o_reset = unpack_out_host(arr[sh], mm)
            sort_idx = order_of[sh]
            c_limit = limits_of[sh]
            over = 0
            for pos in range(mm):
                sj = int(sort_idx[pos])
                i = members[sh][sj][0]
                st = int(o_status[pos])
                if st == _OVER_I:
                    over += 1
                responses[i] = RateLimitResp(
                    status=_STATUS_OF[st],
                    limit=int(c_limit[sj]),
                    remaining=int(o_rem[pos]),
                    reset_time=int(o_reset[pos]),
                )
            self.over_limit_total += over
        for sh, (e_slots, e_exps) in enumerate(host_expire):
            if e_slots:
                self.tables[sh].set_expiry(
                    np.asarray(e_slots, dtype=_I32), np.asarray(e_exps, dtype=_I64)
                )

    SWEEP_WINDOW = 1 << 17  # see DecisionEngine.SWEEP_WINDOW

    def sweep(
        self, now_ms: Optional[int] = None, max_windows: Optional[int] = None
    ) -> int:
        """Reclaim slots of expired buckets on every shard; returns the
        number freed (sharded counterpart of DecisionEngine.sweep).

        Windowed device-side compaction of every shard's rows, the
        window at one shard-local start on all of them: host transfer
        per window is one count vector [n_shards] plus only the freed
        indices (VERDICT r1 item 4)."""
        from gubernator_tpu.ops.expiry import windowed_sweep

        if now_ms is None:
            now_ms = self.clock.now_ms()

        def release(order, counts, start) -> int:
            counts_np = np.asarray(counts)
            total = 0
            for sh in np.nonzero(counts_np)[0]:
                c = int(counts_np[sh])
                slots = np.asarray(order[sh, :c]).astype(np.int64) + start
                self.tables[sh].release_slots(slots)
                total += c
            return total

        # guberlint: ok drift — sharded twin of engine.py's engine.sweep site
        with self._lock, self._stage("engine.sweep") as st:
            freed = windowed_sweep(
                self, self.shard_capacity, now_ms, max_windows, release,
                scan=self._sweep_scan, commit=self._sweep_commit,
            )
            if st.span is not None:
                st.span.set_attribute("freed", freed)
            return freed

    def warmup(self, max_width: int = 1024) -> None:
        """Pre-compile the sharded step for padded widths up to
        `max_width` per shard and the clear ladder (see
        DecisionEngine.warmup).  Keys are picked so each shard gets
        exactly `width` of them — hashing arbitrary keys would leave
        the per-shard count fluctuating around `width` and compile the
        wrong padded widths."""
        saved = (
            self.requests_total,
            self.batches_total,
            self.rounds_total,
            self.dispatches_total,
            [(t.hits, t.misses, t.evictions, t.unexpired_evictions)
             for t in self.tables],
        )
        # Warmup traffic must not reach a write-through Store (it would
        # persist junk __warmup__ keys and pay external round-trips).
        saved_store, self.store = self.store, None
        try:
            # Pre-assign keys per shard by rejection sampling once, at the
            # largest width; smaller widths use prefixes.
            per_shard: List[List[str]] = [[] for _ in range(self.n_shards)]
            i = 0
            while any(len(ks) < max_width for ks in per_shard):
                req = RateLimitReq(name="__warmup__", unique_key=f"{i}")
                sh = self.shard_of(req.hash_key())
                if len(per_shard[sh]) < max_width:
                    per_shard[sh].append(req.unique_key)
                i += 1
            now = self.clock.now_ms()
            width = 64
            while width <= max_width:
                reqs = [
                    RateLimitReq(
                        name="__warmup__",
                        unique_key=k,
                        hits=0,
                        limit=1,
                        duration=1,
                    )
                    for ks in per_shard
                    for k in ks[:width]
                ]
                self.get_rate_limits(reqs, now_ms=now)
                width *= 2
            # Columnar-kernel ladder (the sorted mesh step is a different
            # jitted program than the dataclass-path step; see
            # DecisionEngine.warmup).  Balanced per-shard keys compile the
            # exact [n_shards, width] padded shapes the wire path produces.
            width = 64
            while width <= max_width:
                keys = [
                    f"__warmup___{k}".encode()
                    for ks in per_shard
                    for k in ks[:width]
                ]
                n = len(keys)
                self.apply_columnar(
                    keys,
                    np.zeros(n, dtype=_I32),
                    np.zeros(n, dtype=_I32),
                    np.zeros(n, dtype=_I64),  # hits=0: report-only
                    np.ones(n, dtype=_I64),
                    np.ones(n, dtype=_I64),
                    np.zeros(n, dtype=_I64),
                    now_ms=now,
                )
                width *= 2
            # Duplicate-key ladder: hot-key batches run the per-shard
            # collapsed-segment program, a SEPARATE compile family from
            # the packed step (see DecisionEngine.warmup's
            # b'__warmup__dup' batches) — without it the first hot-key
            # batch on a mesh deployment pays the multi-second XLA
            # compile inside the serving path.  One hot key per shard,
            # reusing the rejection-sampled per-shard keys (the same
            # encoding the columnar ladder above proved routes to each
            # shard), keeps the padded [n_shards, width] shapes
            # identical to serving.
            dup_key = [
                f"__warmup___{ks[0]}".encode() for ks in per_shard
            ]
            width = 64
            while width <= max_width:
                keys = [k for k in dup_key for _ in range(width)]
                n = len(keys)
                self.apply_columnar(
                    keys,
                    np.zeros(n, dtype=_I32),
                    np.zeros(n, dtype=_I32),
                    np.zeros(n, dtype=_I64),
                    np.ones(n, dtype=_I64),
                    np.ones(n, dtype=_I64),
                    np.zeros(n, dtype=_I64),
                    now_ms=now,
                )
                width *= 2
            csize = 16
            cap = self.shard_capacity
            while csize <= max_width:
                dummy = self._put(
                    np.tile(
                        np.arange(cap, cap + csize, dtype=_I64).astype(_I32),
                        (self.n_shards, 1),
                    )
                )
                self._state = self._state._replace(
                    meta=self._clear_step(self._state.meta, dummy)
                )
                csize *= 2
            # Readback-combiner stack ladder (see DecisionEngine.warmup).
            from gubernator_tpu.ops.bucket_kernel import PACKED_OUT_ROWS

            width = 64
            while width <= max_width:
                self.readback.warmup_stacks(
                    (self.n_shards, PACKED_OUT_ROWS, width), jnp.int32
                )
                width *= 2
            if self._use_psum_merge:
                # psum-merge ladder: the balanced warmup batches above
                # only compile (n_pad, width) keys of the balanced
                # form; real client batches produce ANY pow2 pair with
                # width <= n_pad <= n_shards*width.  Compile the whole
                # universe (<= log(widths) x log(n_shards) programs,
                # each tiny) plus the merged replicated readback
                # stacks, so no serve-time batch pays an XLA compile.
                width = 64
                while width <= max_width:
                    n_pad = width
                    # pow2 bound: non-pow2 mesh sizes still pad the
                    # total batch to the next power of two.
                    while n_pad <= _pad_size(width * self.n_shards):
                        prog = self._merge_prog(n_pad, width)
                        # The dummy pout must carry the SAME sharding
                        # as the real step output (P(keys)) — the jit
                        # cache keys on input shardings, and a host-
                        # committed dummy would warm a program the
                        # serve path never hits.
                        pout = self._put(
                            np.zeros(
                                (self.n_shards, PACKED_OUT_ROWS, width),
                                dtype=np.int32,
                            )
                        )
                        pos = np.full(
                            (self.n_shards, width), n_pad, dtype=_I32
                        )
                        np.asarray(prog(pout, self._put(pos)))
                        self.readback.warmup_stacks(
                            (PACKED_OUT_ROWS, n_pad), jnp.int32
                        )
                        n_pad *= 2
                    width *= 2
            self.sweep(now_ms=now + 2)
            (
                self.requests_total,
                self.batches_total,
                self.rounds_total,
                self.dispatches_total,
                table_stats,
            ) = saved
            for t, (h, m, ev, un) in zip(self.tables, table_stats):
                if hasattr(t, "discount_stats"):
                    # Native tables re-mirror cumulative C++ counters on
                    # every schedule(); register discounts instead of
                    # restoring attributes (see DecisionEngine.warmup).
                    t.discount_stats(
                        t.hits - h, t.misses - m, t.evictions - ev,
                        t.unexpired_evictions - un,
                    )
                else:
                    t.hits, t.misses = h, m
                    t.evictions, t.unexpired_evictions = ev, un
            # Wider batches are chunked, not compiled on demand (see
            # DecisionEngine.warmup); the width is a shard's.
            self.max_kernel_width = min(self.max_kernel_width, max_width)
        finally:
            # Exception-safety: a failed warmup must not leave
            # persistence disabled (see DecisionEngine.warmup).
            self.store = saved_store

    # ------------------------------------------------------------------
    # Columnar fast path over the mesh — the multi-chip counterpart of
    # DecisionEngine.apply_columnar: vectorized shard routing (one FNV
    # pass), per-shard native scheduling, host presort per shard, ONE
    # shard_map step per round, one packed readback for the whole mesh.

    def apply_columnar(
        self,
        keys,  # List[bytes] | core.engine.PackedKeys
        algo: np.ndarray,
        behavior: np.ndarray,
        hits: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        burst: np.ndarray,
        now_ms: Optional[int] = None,
        want_async: bool = False,
        route_hashes: Optional[np.ndarray] = None,  # uint64 fnv1a per key
    ):
        if self.store is not None:
            raise RuntimeError(
                "apply_columnar does not support a write-through Store; "
                "use get_rate_limits"
            )
        n = len(keys)
        if now_ms is None:
            now_ms = self.clock.now_ms()
        greg_mask = (behavior & int(Behavior.DURATION_IS_GREGORIAN)) != 0
        if greg_mask.any():
            greg_dur = np.zeros(n, dtype=_I64)
            greg_exp = np.zeros(n, dtype=_I64)
            now_dt = dt_from_ms(now_ms)
            for i in np.nonzero(greg_mask)[0]:
                greg_dur[i] = gregorian_duration(now_dt, int(duration[i]))
                greg_exp[i] = gregorian_expiration(now_dt, int(duration[i]))
        else:
            greg_dur = np.zeros(n, dtype=_I64)
            greg_exp = greg_dur

        # guberlint: ok drift — sharded twin of engine.py's engine.lock_wait site
        wait = self._stage("engine.lock_wait", work=False).start()
        with self._lock:
            wait.stop()
            t_held = _time.monotonic()
            # A section of CPU_MIN_ITEMS items or more reads the
            # holder's CPU clock around the hold and in the CPU_STAGES
            # inside it (utils/metrics.py); the same thread takes and
            # lets go of the lock.
            self._cpu_section = n >= CPU_MIN_ITEMS
            c_held = _time.thread_time() if self._cpu_section else None
            try:
                # guberlint: ok drift — sharded twin of engine.py's
                # engine.columnar site
                with span("engine.columnar", batch=n):
                    pending = self._apply_columnar_locked(
                        keys, algo, behavior, hits, limit, duration,
                        burst, greg_dur, greg_exp, greg_mask, now_ms,
                        route_hashes,
                    )
                    self.requests_total += n
                    self.batches_total += 1
            finally:
                # Histogram only: the parent of the leaf stages.
                self._cpu_section = False
                cpu = None if c_held is None else _time.thread_time() - c_held
                self.stages["engine.lock_hold"].observe(
                    _time.monotonic() - t_held, cpu
                )
        return pending if want_async else pending.get()

    def _apply_columnar_locked(
        self, keys, algo, behavior, hits, limit, duration, burst,
        greg_dur, greg_exp, greg_mask, now_ms, route_hashes=None,
    ):
        from gubernator_tpu.core.engine import PackedKeys

        n_sh = self.n_shards
        cap = self.shard_capacity
        n = len(keys)
        packed = keys if isinstance(keys, PackedKeys) else None
        if self._multi_ok:
            if packed is None:
                # Pack the key list once — the native call needs only
                # (buf, offsets) and computes fnv1a itself.
                packed = PackedKeys.from_list(keys)
                route_hashes = None
            return self._apply_columnar_native(
                packed, algo, behavior, hits, limit, duration, burst,
                greg_dur, greg_exp, greg_mask, now_ms, route_hashes,
            )
        if packed is not None and not all(
            hasattr(t, "schedule_packed") for t in self.tables
        ):
            keys = packed.to_list()
            packed = None

        # 1. Vectorized shard routing: one FNV-1a pass over the batch
        # (or the wire codec's precomputed hashes, when given), and the
        # request indices of every shard's lane.
        with self._stage("mesh.route"):
            if route_hashes is not None:
                hashes = np.asarray(route_hashes, dtype=np.uint64)
            else:
                assert packed is None, "PackedKeys requires route_hashes"
                padded, lengths = pack_keys(keys)
                hashes = fnv1a_64_batch(padded, lengths)
            shards = (hashes % np.uint64(n_sh)).astype(np.int64)
            shard_idx: List[np.ndarray] = [
                np.nonzero(shards == sh)[0] for sh in range(n_sh)
            ]

        # 2. Per-shard native scheduling.
        # guberlint: ok drift — sharded twin of engine.py's engine.intern site
        with self._stage("engine.intern"):
            shard_slots: List[np.ndarray] = []
            shard_rounds: List[np.ndarray] = []
            clear_by_round: Dict[int, List[List[int]]] = {}
            max_round = 0
            for sh in range(n_sh):
                idx = shard_idx[sh]
                if len(idx) == 0:
                    shard_slots.append(np.empty(0, dtype=_I32))
                    shard_rounds.append(np.empty(0, dtype=_I32))
                    continue
                table = self.tables[sh]
                if packed is not None:
                    slots, rounds, evicted, evict_rounds = table.schedule_packed(
                        packed.buf, packed.offsets, now_ms,
                        idx=idx.astype(np.int64),
                    )
                elif hasattr(table, "schedule"):
                    slots, rounds, evicted, evict_rounds = table.schedule(
                        [keys[i] for i in idx], now_ms
                    )
                else:
                    slots = np.empty(len(idx), dtype=_I32)
                    rounds = np.empty(len(idx), dtype=_I32)
                    seq: Dict[int, int] = {}
                    ev_list: List[int] = []
                    ev_rounds: List[int] = []
                    for j, i in enumerate(idx):
                        cleared: List[int] = []
                        slot = table.intern(keys[i].decode(), now_ms, cleared)
                        for es in cleared:
                            ev_list.append(es)
                            ev_rounds.append(seq.get(es, 0))
                        k = seq.get(slot, 0)
                        seq[slot] = k + 1
                        slots[j] = slot
                        rounds[j] = k
                    evicted = np.asarray(ev_list, dtype=_I32)
                    evict_rounds = np.asarray(ev_rounds, dtype=_I32)
                shard_slots.append(slots)
                shard_rounds.append(rounds)
                if len(rounds):
                    max_round = max(max_round, int(rounds.max()))
                for es, k in zip(evicted.tolist(), evict_rounds.tolist()):
                    clear_by_round.setdefault(k, [[] for _ in range(n_sh)])[
                        sh
                    ].append(es)

        # 2b. Hot keys: collapse uniform duplicate segments per shard
        # into one mesh dispatch per chunk (see core.engine
        # _try_collapse and bucket_kernel's closed form).
        pieces: Optional[List[tuple]] = None
        if max_round > 0:
            pieces = self._try_collapse_sharded(
                shard_idx, shard_slots, clear_by_round,
                algo, behavior, hits, limit, duration, burst,
                greg_dur, greg_exp, now_ms,
            )
        from gubernator_tpu.core.engine import PendingColumnar

        def set_expiry() -> None:
            # TTL mirror, per shard.
            # guberlint: ok drift — sharded twin of engine.py's engine.set_expiry site
            with self._stage("engine.set_expiry"):
                expires = np.where(
                    greg_mask, greg_exp, now_ms + duration
                ).astype(_I64)
                for sh in range(n_sh):
                    if len(shard_idx[sh]):
                        self.tables[sh].set_expiry(
                            shard_slots[sh], expires[shard_idx[sh]]
                        )

        if pieces is not None:
            set_expiry()
            return PendingColumnar(self, pieces, limit, n)

        # 3. One mesh step per round (chunked by max_kernel_width).
        pieces = []
        for k in range(max_round + 1):
            # guberlint: ok drift — sharded twin of engine.py's engine.pack site
            with self._stage("engine.pack"):
                members = [
                    shard_idx[sh][shard_rounds[sh] == k]
                    if len(shard_idx[sh]) else shard_idx[sh]
                    for sh in range(n_sh)
                ]
                m_slots = [
                    shard_slots[sh][shard_rounds[sh] == k]
                    if len(shard_slots[sh])
                    else shard_slots[sh]
                    for sh in range(n_sh)
                ]
            if not any(len(m) for m in members) and k not in clear_by_round:
                continue
            clears = clear_by_round.get(k)
            if clears is not None:
                self._apply_shard_clears(clears)
            offset = 0
            while True:
                chunk_members = [
                    m[offset : offset + self.max_kernel_width] for m in members
                ]
                chunk_slots = [
                    s[offset : offset + self.max_kernel_width] for s in m_slots
                ]
                if offset > 0 and not any(len(m) for m in chunk_members):
                    break
                whole_batch = (
                    max_round == 0
                    and offset == 0
                    and all(
                        len(m) <= self.max_kernel_width for m in members
                    )
                )
                pieces.append(
                    self._dispatch_sorted_chunk(
                        chunk_members, chunk_slots,
                        algo, behavior, hits, limit, duration, burst,
                        greg_dur, greg_exp, now_ms,
                        merge_n=n if whole_batch else None,
                    )
                )
                self.rounds_total += 1
                offset += self.max_kernel_width
                if all(offset >= len(m) for m in members):
                    break

        set_expiry()  # 4.
        return PendingColumnar(self, pieces, limit, n)

    def _apply_columnar_native(
        self, packed, algo, behavior, hits, limit, duration, burst,
        greg_dur, greg_exp, greg_mask, now_ms, route_hashes,
    ):
        """The whole host tier in ONE FFI call (git_multi_schedule):
        shard routing, per-table interning/LRU/eviction, round
        assignment, TTL mirror writes, and the shard-grouped
        (slot, round)-sorted dispatch order.  Replaces the per-shard
        Python loop of nonzero/schedule/set_expiry/argsort calls —
        the serialized host work VERDICT r4 weak #3 measured at ~5ms
        per 8-shard batch on a one-core host."""
        from gubernator_tpu.core.engine import PendingColumnar
        from gubernator_tpu.core.native import multi_schedule

        n_sh = self.n_shards
        n = len(packed.offsets) - 1
        # One FFI call: the TTL mirror's writes ride it, so this route
        # has no engine.set_expiry of its own.
        with self._stage("engine.intern"):
            expires = np.where(
                greg_mask, greg_exp, np.int64(now_ms) + duration
            ).astype(_I64)
            (max_round, _shard, slots, rounds, order, counts,
             evicted, evict_shard, evict_rounds) = multi_schedule(
                self.tables, packed.buf, packed.offsets, route_hashes,
                now_ms, expires,
            )
        # The FFI call has hashed and grouped; what is left of the
        # router on the host is cutting its order into per-shard lanes.
        with self._stage("mesh.route"):
            flat = self._single_program and self._flat_ok
            if flat:
                # Globalize slots: shard*cap + slot.  The concatenated
                # order array is then globally slot-sorted (global slot
                # is monotone in (shard, slot)), so the whole batch
                # dispatches as ONE flat program — no per-shard padded
                # blocks.
                gslots = (
                    slots.astype(np.int64)
                    + _shard.astype(np.int64) * self.shard_capacity
                ).astype(_I32)
                segs = [order]
                seg_slots = gslots
            else:
                bounds = np.zeros(n_sh + 1, dtype=np.int64)
                np.cumsum(counts, out=bounds[1:])
                segs = [
                    order[bounds[sh]:bounds[sh + 1]] for sh in range(n_sh)
                ]
                seg_slots = slots
            lane_slots = [seg_slots[seg] for seg in segs]
            clear_by_round: Dict[int, List[List[int]]] = {}
            for s, sh, k in zip(
                evicted.tolist(), evict_shard.tolist(), evict_rounds.tolist()
            ):
                clear_by_round.setdefault(k, [[] for _ in range(n_sh)])[
                    sh
                ].append(s)

        if max_round > 0:
            pieces = self._collapse_presorted(
                [
                    (seg, sl) if len(seg) else None
                    for seg, sl in zip(segs, lane_slots)
                ],
                clear_by_round, algo, behavior, hits, limit,
                duration, burst, greg_dur, greg_exp, now_ms, flat=flat,
            )
            if pieces is not None:
                return PendingColumnar(self, pieces, limit, n)

        pieces = []
        for k in range(max_round + 1):
            if max_round == 0:
                members, m_slots = segs, lane_slots
            else:
                with self._stage("engine.pack"):
                    # Round filtering preserves the per-shard slot sort.
                    members = [
                        seg[rounds[seg] == k] if len(seg) else seg
                        for seg in segs
                    ]
                    m_slots = [seg_slots[m] for m in members]
            if not any(len(m) for m in members) and k not in clear_by_round:
                continue
            clears = clear_by_round.get(k)
            if clears is not None:
                self._apply_shard_clears(clears)
            offset = 0
            while True:
                chunk_members = [
                    m[offset : offset + self.max_kernel_width]
                    for m in members
                ]
                chunk_slots = [
                    s[offset : offset + self.max_kernel_width]
                    for s in m_slots
                ]
                if offset > 0 and not any(len(m) for m in chunk_members):
                    break
                whole_batch = (
                    max_round == 0
                    and offset == 0
                    and all(
                        len(m) <= self.max_kernel_width for m in members
                    )
                )
                pieces.append(
                    self._dispatch_sorted_chunk(
                        chunk_members, chunk_slots,
                        algo, behavior, hits, limit, duration, burst,
                        greg_dur, greg_exp, now_ms, presorted=True,
                        flat=flat,
                        merge_n=n if whole_batch else None,
                    )
                )
                self.rounds_total += 1
                offset += self.max_kernel_width
                if all(offset >= len(m) for m in members):
                    break
        return PendingColumnar(self, pieces, limit, n)

    def _collapse_dataclass_sharded(
        self,
        requests: Sequence[RateLimitReq],
        valid: List[int],
        rounds: Dict[int, List[List[Tuple[int, int]]]],
        clear_rounds: Dict[int, List[List[int]]],
        greg_dur: np.ndarray,
        greg_exp: np.ndarray,
        now_ms: int,
        responses: List[Optional[RateLimitResp]],
    ) -> bool:
        """Hot-key batches on the sharded dataclass path: build columns
        once and reuse the sharded collapse.  Returns False for the
        rounds fallback (see core.engine._collapse_dataclass)."""
        from gubernator_tpu.ops.bucket_kernel import unpack_out_host
        if any(k > 0 for k in clear_rounds):
            return False
        n_sh = self.n_shards
        nv = len(valid)
        pos_of = {i: j for j, i in enumerate(valid)}
        c_algo = np.empty(nv, dtype=_I32)
        c_beh = np.empty(nv, dtype=_I32)
        c_hits = np.empty(nv, dtype=_I64)
        c_limit = np.empty(nv, dtype=_I64)
        c_dur = np.empty(nv, dtype=_I64)
        c_burst = np.empty(nv, dtype=_I64)
        c_gdur = np.empty(nv, dtype=_I64)
        c_gexp = np.empty(nv, dtype=_I64)
        expire = np.empty(nv, dtype=_I64)
        for j, i in enumerate(valid):
            r = requests[i]
            c_algo[j] = int(r.algorithm)
            beh = int(r.behavior)
            c_beh[j] = beh
            c_hits[j] = r.hits
            c_limit[j] = r.limit
            c_dur[j] = r.duration
            c_burst[j] = r.burst
            c_gdur[j] = greg_dur[i]
            c_gexp[j] = greg_exp[i]
            expire[j] = greg_exp[i] if beh & _GREG else now_ms + r.duration

        # Rebuild per-shard (column positions, slots) in arrival order.
        shard_idx: List[np.ndarray] = []
        shard_slots: List[np.ndarray] = []
        per_shard: List[List[Tuple[int, int]]] = [[] for _ in range(n_sh)]
        for k in sorted(rounds):
            for sh in range(n_sh):
                per_shard[sh].extend(rounds[k][sh])
        for sh in range(n_sh):
            # Arrival order within a key is the ROUND order (k ascending
            # per slot); restore global arrival order by request index.
            items = sorted(per_shard[sh], key=lambda t: pos_of[t[0]])
            shard_idx.append(
                np.asarray([pos_of[i] for i, _ in items], dtype=np.int64)
            )
            shard_slots.append(
                np.asarray([s for _, s in items], dtype=_I32)
            )

        # guberlint: ok drift — sharded twin of engine.py's
        # engine.collapsed site
        with span("engine.collapsed", width=nv):
            pieces = self._try_collapse_sharded(
                shard_idx, shard_slots, clear_rounds,
                c_algo, c_beh, c_hits, c_limit, c_dur, c_burst,
                c_gdur, c_gexp, now_ms,
            )
        if pieces is None:
            return False
        over = 0
        for pout, dst_rows, chunk_m, _width in pieces:
            arr = pout.fetch()
            for sh in range(n_sh):
                mm = chunk_m[sh]
                if mm == 0:
                    continue
                st, rem, rst = unpack_out_host(arr[sh], mm)
                for p, j in enumerate(dst_rows[sh].tolist()):
                    i = valid[j]
                    s = int(st[p])
                    if s == _OVER_I:
                        over += 1
                    responses[i] = RateLimitResp(
                        status=_STATUS_OF[s],
                        limit=int(c_limit[j]),
                        remaining=int(rem[p]),
                        reset_time=int(rst[p]),
                    )
        self.over_limit_total += over
        for sh in range(n_sh):
            if len(shard_idx[sh]):
                self.tables[sh].set_expiry(
                    shard_slots[sh], expire[shard_idx[sh]]
                )
        return True

    def _try_collapse_sharded(
        self, shard_idx, shard_slots, clear_by_round,
        algo, behavior, hits, limit, duration, burst,
        greg_dur, greg_exp, now_ms,
    ) -> Optional[List[tuple]]:
        """Per-shard duplicate-segment collapse; returns pieces or None
        for the rounds fallback (same preconditions as the single-device
        engine's _try_collapse)."""
        with self._stage("engine.pack"):
            per_shard: List[Optional[tuple]] = []
            for sh in range(self.n_shards):
                idx = shard_idx[sh]
                if len(idx) == 0:
                    per_shard.append(None)
                    continue
                order = np.argsort(shard_slots[sh], kind="stable")
                per_shard.append((idx[order], shard_slots[sh][order]))
        return self._collapse_presorted(
            per_shard, clear_by_round, algo, behavior, hits, limit,
            duration, burst, greg_dur, greg_exp, now_ms,
        )

    def _collapse_presorted(
        self, per_shard, clear_by_round,
        algo, behavior, hits, limit, duration, burst,
        greg_dur, greg_exp, now_ms, flat=False,
    ) -> Optional[List[tuple]]:
        """Collapse over per-shard (src, s_slots) pairs already sorted
        by (slot, arrival) — the native multi_schedule order, or the
        argsort in _try_collapse_sharded.  flat=True: one pseudo-shard
        of globalized slots (see _dispatch_sorted_chunk).  engine.pack
        is observed slice by slice (the gate, then each chunk's pack),
        never around a dispatch: chunk k+1 packs while the mesh runs
        chunk k."""
        from gubernator_tpu.ops.bucket_kernel import (
            COLLAPSED_IN_ROWS,
            pack_collapsed_host,
        )
        from gubernator_tpu.types import Algorithm

        if any(k > 0 for k in clear_by_round):
            return None  # mid-batch slot reuse
        n_sh = 1 if flat else self.n_shards
        cap = self.capacity if flat else self.shard_capacity
        cols = (algo, behavior, hits, limit, duration, burst,
                greg_dur, greg_exp)
        rst_bit = int(Behavior.RESET_REMAINING)
        leaky = int(Algorithm.LEAKY_BUCKET)

        with self._stage("engine.pack"):
            for p in per_shard:
                if p is None:
                    continue
                src, s_slots = p
                uniq, seg_start, counts = np.unique(
                    s_slots, return_index=True, return_counts=True
                )
                seg_of = np.repeat(
                    np.arange(len(uniq), dtype=np.int64), counts
                )
                dup = counts[seg_of] > 1
                for col in cols:
                    cs = col[src]
                    if not np.array_equal(
                        cs[dup], cs[seg_start][seg_of][dup]
                    ):
                        return None
                beh_s = behavior[src]
                if bool((((beh_s & rst_bit) != 0) & dup).any()):
                    return None
                if bool(
                    (((algo[src] == leaky) & (hits[src] < 0)) & dup).any()
                ):
                    return None
            max_lanes = max(
                (len(p[0]) for p in per_shard if p is not None), default=0
            )

        clears = clear_by_round.get(0)
        if clears is not None:
            self._apply_shard_clears(clears)

        program = (
            self._flat_collapsed_fused if flat else self._collapsed_fused
        )
        pieces: List[tuple] = []
        empty64 = np.empty(0, dtype=_I64)
        for lo in range(0, max_lanes, self.max_kernel_width):
            with self._stage("engine.pack"):
                chunk_m = [
                    min(max(len(p[0]) - lo, 0), self.max_kernel_width)
                    if p is not None
                    else 0
                    for p in per_shard
                ]
                width = _pad_size(max(chunk_m))
                buf = np.zeros((n_sh, COLLAPSED_IN_ROWS, width), dtype=_I32)
                dst_rows: List[np.ndarray] = []
                for sh in range(n_sh):
                    m = chunk_m[sh]
                    if m == 0:
                        pack_collapsed_host(
                            width, now_ms, cap, np.empty(0, dtype=_I32),
                            empty64,
                            (empty64,) * 8,
                            np.empty(0, dtype=_I32),
                            np.empty(0, dtype=_I32),
                            out=buf[sh],
                        )
                        dst_rows.append(np.empty(0, dtype=np.int64))
                        continue
                    src, s_slots = per_shard[sh]
                    c_src = src[lo : lo + m]
                    c_slots = s_slots[lo : lo + m]
                    c_uniq, c_start, c_counts = np.unique(
                        c_slots, return_index=True, return_counts=True
                    )
                    c_seg_of = np.repeat(
                        np.arange(len(c_uniq), dtype=np.int64), c_counts
                    )
                    c_pos = np.arange(m, dtype=np.int64) - c_start[c_seg_of]
                    pack_collapsed_host(
                        width, now_ms, cap,
                        np.ascontiguousarray(c_uniq, dtype=_I32),
                        c_counts.astype(np.int64),
                        tuple(col[c_src][c_start] for col in cols),
                        c_seg_of.astype(_I32),
                        c_pos.astype(_I32),
                        out=buf[sh],
                    )
                    dst_rows.append(c_src)

            t0 = _time.monotonic()
            pout = self._step(program, self._put(buf))
            self.round_duration.observe(_time.monotonic() - t0)
            self.rounds_total += 1
            pieces.append(
                (self.readback.register(pout), dst_rows, chunk_m, width)
            )
        return pieces

    def _merge_prog(self, n_pad: int, width: int):
        """Jitted psum column merge: per-shard packed outputs
        [n_shards, PACKED_OUT_ROWS, width] + per-shard request
        positions [n_shards, width] (padding = out-of-range, dropped)
        → ONE replicated request-ordered [PACKED_OUT_ROWS, n_pad]
        buffer.  Each request index appears on exactly one shard, so
        the scatter-then-psum is an exact merge."""
        key = (n_pad, width)
        prog = self._merge_progs.get(key)
        if prog is None:
            from gubernator_tpu.ops.bucket_kernel import PACKED_OUT_ROWS

            pspec = P(KEYS_AXIS)

            def local_merge(pout, pos):
                base = jnp.zeros((PACKED_OUT_ROWS, n_pad), dtype=jnp.int32)
                own = base.at[:, pos[0]].set(pout[0], mode="drop")
                return jax.lax.psum(own, KEYS_AXIS)

            # guberlint: shapes pout [n_shards, PACKED_OUT_ROWS, W], pos [n_shards, W]; n_pad/W pinned by the cache key (pow2 ladders)
            prog = jax.jit(
                _shard_map(
                    local_merge,
                    mesh=self.mesh,
                    in_specs=(pspec, pspec),
                    out_specs=P(),
                )
            )
            self._merge_progs[key] = prog
        return prog

    def _dispatch_sorted_chunk(
        self, members, m_slots, algo, behavior, hits, limit, duration,
        burst, greg_dur, greg_exp, now_ms, presorted=False, flat=False,
        merge_n=None,
    ):
        """Pack one presorted [n_sh, PACKED_IN_ROWS, width] round
        buffer, dispatch the packed mesh step (one h2d + one or two
        kernels + one async d2h for the WHOLE mesh), start the async
        readback.  Returns a PendingColumnar piece:
        (packed, dst_idx rows, m per shard, width).

        flat=True (single-program mode): members is ONE pseudo-shard of
        globalized slots; the buffer is [1, PACKED_IN_ROWS, width] and
        the flat executors reshape state to [capacity] inside jit."""
        from gubernator_tpu.ops.bucket_kernel import (
            PACKED_IN_ROWS,
            pack_batch_host,
        )

        n_sh = 1 if flat else self.n_shards
        cap = self.capacity if flat else self.shard_capacity
        merging = merge_n is not None and self._use_psum_merge and not flat

        with self._stage("engine.pack"):
            width = _pad_size(max((len(m) for m in members), default=1))
            buf = np.zeros((n_sh, PACKED_IN_ROWS, width), dtype=_I32)
            dst_rows = []
            empty_cols = np.empty(0, dtype=_I64)
            for sh in range(n_sh):
                m = len(members[sh])
                if m == 0:
                    dst_rows.append(np.empty(0, dtype=np.int64))
                    pack_batch_host(
                        width, now_ms, cap, np.empty(0, dtype=_I32),
                        empty_cols, empty_cols, empty_cols, empty_cols,
                        empty_cols, empty_cols, empty_cols, empty_cols,
                        out=buf[sh],
                    )
                    continue
                if presorted:
                    idx_sorted = members[sh]
                    slots_sorted = m_slots[sh]
                else:
                    order = np.argsort(m_slots[sh], kind="stable")
                    idx_sorted = members[sh][order]
                    slots_sorted = m_slots[sh][order]
                pack_batch_host(
                    width,
                    now_ms,
                    cap,
                    np.ascontiguousarray(slots_sorted, dtype=_I32),
                    algo[idx_sorted],
                    behavior[idx_sorted],
                    hits[idx_sorted],
                    limit[idx_sorted],
                    duration[idx_sorted],
                    burst[idx_sorted],
                    greg_dur[idx_sorted],
                    greg_exp[idx_sorted],
                    out=buf[sh],
                )
                dst_rows.append(idx_sorted)

        t0 = _time.monotonic()
        pout = self._step(
            self._flat_fused if flat else self._packed_fused,
            self._put(buf),
        )
        if merging:
            # psum GLOBAL merge: scatter every shard's lanes to their
            # request positions on device and sum across the mesh —
            # one replicated, already-request-ordered readback.  The
            # positions pack while the mesh runs the step.
            with self._stage("engine.pack"):
                n_pad = _pad_size(merge_n)
                pos = np.full((n_sh, width), n_pad, dtype=_I32)
                for sh in range(n_sh):
                    if len(dst_rows[sh]):
                        pos[sh, : len(dst_rows[sh])] = dst_rows[sh]
            pos_dev = self._put(pos)
            merge = self._merge_prog(n_pad, width)
            with self._stage("device.launch"):
                merged = merge(pout, pos_dev)
                self.dispatches_total += 1
            self.round_duration.observe(_time.monotonic() - t0)
            return (
                self.readback.register(merged),
                np.arange(merge_n, dtype=np.int64),
                merge_n,
                n_pad,
            )
        self.round_duration.observe(_time.monotonic() - t0)
        return (
            self.readback.register(pout), dst_rows,
            [len(m) for m in members], width,
        )

    # ------------------------------------------------------------------
    # Bulk persistence (Loader; reference: store.go:69-78).  A columnar
    # restore goes a piece at a time, shard by shard; the per-item
    # restore and `save` use one full host round trip of the state.

    def load(self, loader) -> int:
        """Stream the cache in before serving; returns rows restored.
        A loader that hands over columns (`load_columns`, store.py) is
        restored a piece at a time (`_load_columns`); one that has only
        `load()` item by item.  Either way each key goes to the shard
        `shard_of` names, a shard's part of the stream in order is its
        LRU order (first row oldest), a key seen twice keeps its last
        row, and a shard given more rows than it holds evicts its
        oldest as served misses do (reference: store.go:69-78,
        lrucache.go:148-159)."""
        columns = getattr(loader, "load_columns", None)
        if columns is not None:
            return self._load_columns(columns())
        from gubernator_tpu.store import LeakyBucketItem, TokenBucketItem

        from gubernator_tpu.ops.bucket_kernel import (
            pack_state_host,
            unpack_state_host,
        )

        now_ms = self.clock.now_ms()
        with self._lock:
            # Decode the current state into logical columns, apply the
            # stream, re-encode once — bulk startup path, O(state) by
            # design.
            host = unpack_state_host(self._host_state())
            host = {k: np.array(v) for k, v in host.items()}  # writable
            count = 0
            for item in loader.load():
                v = item.value
                if v is None or not item.key:
                    continue
                sh = self.shard_of(item.key)
                cleared: List[int] = []
                slot = self.tables[sh].intern(item.key, now_ms, cleared)
                for es in cleared:
                    host["occupied"][sh, es] = False
                self.tables[sh].set_expiry(
                    np.asarray([slot], dtype=_I32),
                    np.asarray([item.expire_at], dtype=_I64),
                )
                host["occupied"][sh, slot] = True
                host["algo"][sh, slot] = int(item.algorithm)
                host["limit"][sh, slot] = v.limit
                host["duration"][sh, slot] = v.duration
                host["expire"][sh, slot] = item.expire_at
                host["invalid"][sh, slot] = item.invalid_at
                if isinstance(v, TokenBucketItem):
                    host["status"][sh, slot] = v.status
                    host["remaining"][sh, slot] = v.remaining
                    host["remf_hi"][sh, slot] = 0
                    host["remf_lo"][sh, slot] = 0
                    host["t0"][sh, slot] = v.created_at
                    host["burst"][sh, slot] = 0
                elif isinstance(v, LeakyBucketItem):
                    host["status"][sh, slot] = 0
                    from gubernator_tpu.store import words_from_float

                    w = (
                        v.remaining_words
                        if v.remaining_words is not None
                        else words_from_float(v.remaining)
                    )
                    host["remf_hi"][sh, slot] = w[0]
                    host["remf_lo"][sh, slot] = np.uint32(w[1])
                    host["t0"][sh, slot] = v.updated_at
                    host["burst"][sh, slot] = v.burst
                count += 1
            packed = pack_state_host(host)
            self._state = BucketState(
                **{f: self._put(a.reshape(-1)) for f, a in packed.items()}
            )
            self.rows_loaded_total += count
        return count

    def _load_columns(self, chunks) -> int:
        """The columnar restore (DecisionEngine's, per shard): a piece
        of at most `load_width` rows of a chunk is routed by FNV-1a as
        `shard_of` routes a key, each shard's rows go into its intern
        table in one `load_rows` (arrival order = LRU order, evictions
        counted as `intern`'s, the TTL mirror written row by row), and
        one donated `_load_step` scatters the piece's [n_shards,
        load_width] record.  The state never crosses to the host and
        no clear runs: an evicted slot goes at once to the row that
        evicted it, and a restore writes every column of its row.  The
        shards' tables are independent and `load_rows` leaves the
        interpreter lock, so a piece's shards are placed on threads of
        their own."""
        from concurrent.futures import ThreadPoolExecutor

        count = 0
        now_ms = self.clock.now_ms()
        with self._lock, ThreadPoolExecutor(
            self.n_shards, thread_name_prefix="guber-load"
        ) as pool:
            for chunk in chunks:
                for lo in range(0, len(chunk), self.load_width):
                    # guberlint: ok drift — sharded twin of engine.py's engine.load site
                    with self._stage("engine.load"):
                        count += self._load_piece(chunk, lo, now_ms, pool)
        return count

    def _load_piece(self, chunk, lo: int, now_ms: int, pool) -> int:  # guberlint: holds _lock
        from gubernator_tpu.ops.bucket_kernel import SlotRecord
        from gubernator_tpu.store import COLUMN_DTYPES

        hi = min(lo + self.load_width, len(chunk))
        starts = chunk.key_offsets[lo:hi]
        lengths = chunk.key_offsets[lo + 1 : hi + 1] - starts
        # A row without a key restores nothing (and holds no byte).
        keyed = np.flatnonzero(lengths > 0)
        rows, starts, lengths = keyed + lo, starts[keyed], lengths[keyed]
        # The piece's keys as a zero-padded [rows, longest] matrix:
        # what `fnv1a_64_batch` hashes, and each shard's keys cut from.
        # Keys of one length lie end to end, and the matrix is a view.
        one_length = len(rows) > 0 and (lengths == lengths[0]).all()
        if one_length:
            key_len = int(lengths[0])
            keys = chunk.key_buf[
                starts[0] : starts[0] + len(rows) * key_len
            ].reshape(len(rows), key_len)
        else:
            lanes = np.arange(int(lengths.max()) if len(rows) else 0)
            in_key = lanes < lengths[:, None]
            keys = chunk.key_buf[np.minimum(
                starts[:, None] + lanes, max(len(chunk.key_buf) - 1, 0)
            )]
            keys[~in_key] = 0
        shard_of = fnv1a_64_batch(keys, lengths) % np.uint64(self.n_shards)

        n_sh, cap, width = self.n_shards, self.shard_capacity, self.load_width
        # Padding lanes: distinct ascending out-of-range slots, dropped.
        rec = {"slot": np.tile(
            np.arange(cap, cap + width, dtype=_I64).astype(_I32), (n_sh, 1)
        )}
        for name, dtype in COLUMN_DTYPES.items():
            rec[name] = np.zeros((n_sh, width), dtype=dtype)

        def place(sh: int) -> int:
            """Shard sh's rows of the piece into its table and its row
            of the record; returns how many."""
            mine = np.flatnonzero(shard_of == sh)
            if not len(mine):
                return 0
            offsets = np.zeros(len(mine) + 1, dtype=_I64)
            np.cumsum(lengths[mine], out=offsets[1:])
            src = rows[mine]
            slots = self.tables[sh].load_rows(
                keys[mine].reshape(-1) if one_length
                else keys[mine][in_key[mine]],
                offsets, chunk.expire_at[src], now_ms,
            )
            n = len(slots)
            if n > 1 and not (slots[1:] > slots[:-1]).all():
                # The scatter wants a shard's slots ascending and each
                # once: of a slot's rows (a key seen twice, a slot
                # evicted and given away inside the piece) the last
                # one is its state.
                order = np.argsort(slots, kind="stable")
                last = np.ones(n, dtype=bool)
                last[:-1] = slots[order][1:] != slots[order][:-1]
                keep = order[last]
                src, slots = src[keep], slots[keep]
            rec["slot"][sh, : len(slots)] = slots
            for name in COLUMN_DTYPES:
                rec[name][sh, : len(slots)] = getattr(chunk, name)[src]
            return n

        n = sum(pool.map(place, range(n_sh)))
        # Uploaded and launched inside this stage: `engine.load` is the
        # leaf (no device.h2d / device.launch under it).
        self._state = self._load_step(
            self._state,
            SlotRecord(**{
                name: jax.device_put(a, self._placement)
                for name, a in rec.items()
            }),
        )
        self.dispatches_total += 1
        self.rows_loaded_total += n
        return n

    def export_items(self):
        """Full-fidelity snapshot as CacheItems (all shards)."""
        from gubernator_tpu.store import CacheItem, LeakyBucketItem, TokenBucketItem
        from gubernator_tpu.types import Algorithm

        with self._lock:
            from gubernator_tpu.ops.bucket_kernel import unpack_state_host

            u = unpack_state_host(self._host_state())
            occ = u["occupied"]
            algo = u["algo"]
            status = u["status"]
            limit = u["limit"]
            remaining = u["remaining"]
            remf_hi = u["remf_hi"]
            remf_lo = u["remf_lo"]
            duration = u["duration"]
            t0 = u["t0"]
            expire = u["expire"]
            burst = u["burst"]
            invalid = u["invalid"]
            located = [
                (sh, int(sl), self.tables[sh].key_for_slot(int(sl)))
                for sh, sl in zip(*np.nonzero(occ))
            ]
        from gubernator_tpu.store import item_from_record

        for sh, sl, key in located:
            if key is None:
                continue
            yield item_from_record(
                key=key,
                algorithm=int(algo[sh, sl]),
                status=int(status[sh, sl]),
                limit=int(limit[sh, sl]),
                remaining=int(remaining[sh, sl]),
                remf_hi=int(remf_hi[sh, sl]),
                remf_lo=int(remf_lo[sh, sl]),
                duration=int(duration[sh, sl]),
                t0=int(t0[sh, sl]),
                expire_at=int(expire[sh, sl]),
                burst=int(burst[sh, sl]),
                invalid_at=int(invalid[sh, sl]),
            )

    def save(self, loader) -> None:
        loader.save(self.export_items())

    def cache_size(self) -> int:
        return sum(len(t) for t in self.tables)

    def close(self) -> None:
        pass
