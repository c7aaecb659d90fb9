"""DecisionEngine — the local rate-limit execution engine.

Replaces the reference's worker pool + per-key algorithm calls
(reference: gubernator_pool.go:250-336 → algorithms.go) with:

  host: key interning (key string → device slot) + batch assembly
  device: one donated step program per round (ops/bucket_kernel.py)

Per-key serialization — which the reference gets from its worker hash
ring (reference: gubernator_pool.go:19-37,183-187) — is preserved by
splitting a batch into *rounds*: request i goes to round k if it is the
k-th occurrence of its key within the batch, so each kernel call sees a
slot at most once and duplicate keys are applied in arrival order,
exactly like the reference's per-worker FIFO.

The engine never reads the wall clock on device: `now_ms` flows in from
the caller (or the injected Clock), enabling frozen-clock conformance
tests (SURVEY.md §4.5).
"""

from __future__ import annotations

import logging
import threading
import time as _time
from contextlib import nullcontext
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.clock import SYSTEM_CLOCK, Clock
from gubernator_tpu.gregorian import (
    GregorianError,
    dt_from_ms,
    gregorian_duration,
    gregorian_expiration,
)
from gubernator_tpu.ops.bucket_kernel import (
    BucketState,
    ProbeVerdict,
    SlotRecord,
    _scatter_form,
    clear_occupied,
    collapsed_step,
    fused_step,
    fused_step_ok,
    load_slots,
    make_state,
    multi_step_ok,
    pack_batch_host,
    pack_collapsed_host,
    uniform_step,
)
from gubernator_tpu.ops.expiry import windowed_sweep
from gubernator_tpu.core.interning import InternTable
from gubernator_tpu.utils.metrics import (
    CPU_MIN_ITEMS,
    CPU_STAGES,
    DurationStat,
    engine_stages,
    stage,
)
from gubernator_tpu.utils.tracing import span
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    Status,
)

log = logging.getLogger("gubernator_tpu.engine")

_I32 = np.int32
_I64 = np.int64

# Hot-loop constants: IntFlag/IntEnum operations cost ~1.5µs each in
# CPython — at 1000-item batches the enum protocol alone was ~15ms per
# wire batch (profiled); plain ints and a lookup table are ~50ns.
_GREG = int(Behavior.DURATION_IS_GREGORIAN)
_OVER_I = int(Status.OVER_LIMIT)
_STATUS_OF = {int(s): s for s in Status}


def _pad_size(n: int, floor: int = 64) -> int:
    """Next power of two ≥ n (bounded set of compiled batch shapes)."""
    size = floor
    while size < n:
        size *= 2
    return size


def _segments_by_unique_keys(keys: List, budget: int) -> List[tuple]:
    """Split a batch into contiguous arrival-order segments of at most
    `budget` UNIQUE keys each (paged mode: unique pages ≤ unique keys,
    so every segment's working set fits the resident frames).  Returns
    [(lo, hi)] half-open ranges covering the batch."""
    segs: List[tuple] = []
    lo = 0
    seen: set = set()
    for i, k in enumerate(keys):
        if k not in seen:
            if len(seen) >= budget:
                segs.append((lo, i))
                lo = i
                seen = set()
            seen.add(k)
    segs.append((lo, len(keys)))
    return segs


class _ZerosCache:
    """Reusable zero arrays (columnar no-greg fast path)."""

    def __init__(self) -> None:
        self._arrays: dict[int, np.ndarray] = {}

    def get(self, n: int) -> np.ndarray:
        a = self._arrays.get(n)
        if a is None:
            a = np.zeros(n, dtype=_I64)
            self._arrays[n] = a
        return a


_ZEROS_CACHE = _ZerosCache()


class PackedKeys:
    """Keys as one concatenated byte buffer + offsets — the native wire
    codec's output format, consumed by the native table's
    schedule_packed without materializing per-key Python objects."""

    __slots__ = ("buf", "offsets", "count")

    def __init__(self, buf: np.ndarray, offsets: np.ndarray, count: int):
        self.buf = buf
        self.offsets = offsets
        self.count = count

    def __len__(self) -> int:
        return self.count

    def to_list(self) -> List[bytes]:
        raw = self.buf.tobytes()
        off = self.offsets
        return [raw[off[i] : off[i + 1]] for i in range(self.count)]

    @classmethod
    def from_list(cls, keys: List[bytes]) -> "PackedKeys":
        """Concatenate a key list into the packed form (the empty-batch
        placeholder keeps a valid base pointer for FFI callees)."""
        n = len(keys)
        buf = b"".join(keys)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(k) for k in keys], out=offsets[1:])
        buf_arr = (
            np.frombuffer(buf, dtype=np.uint8)
            if buf else np.zeros(1, np.uint8)
        )
        return cls(buf_arr, offsets, n)


class PendingColumnar:
    """In-flight columnar batch: device work dispatched, packed outputs
    copying to host asynchronously.  `.get()` materializes (status,
    limit, remaining, reset_time) in request order."""

    __slots__ = ("_engine", "_pieces", "_limit", "_n", "_result", "_reads")

    def __init__(self, engine, pieces, limit, n):
        self._engine = engine
        self._pieces = pieces
        self._limit = limit
        self._n = n
        self._result = None
        self._reads = None

    def start_readback(self) -> "PendingColumnar":
        """Start this batch's own device→host copies now, so that
        "submitted" means launched and copying for every batch shape:
        its readback tickets leave the combiner's queue for transfers
        of their own (`ReadbackCombiner.start_own` — `get()` then waits
        for this batch's steps and no later one's), and pump rounds,
        which launch lazily at the first fetch, are flushed (the flush
        starts its group's copy).  For a caller that launches its next
        batch before it reads this one: the native front's serve
        thread."""
        from gubernator_tpu.core.readback import Ticket

        own = []
        for piece in self._pieces:
            ticket = piece[0]
            if isinstance(ticket, Ticket):
                own.append(ticket)
            elif ticket.group is None and ticket.error is None:
                ticket.pump.flush_for(ticket)
        if own:
            self._reads = self._engine.readback.start_own(own)
        return self

    def get(self):
        if self._result is not None:
            return self._result
        from gubernator_tpu.ops.bucket_kernel import unpack_out_host

        n = self._n
        if self._reads is not None:
            reads, self._reads = self._reads, None
            self._engine.readback.land(reads)
        # The waits first (combined transfers, core/readback.py; each
        # observes device.readback), then ONE engine.unpack for the
        # RPC: a work annotation never spans a blocking read.
        arrs = [piece[0].fetch() for piece in self._pieces]
        with self._engine._stage("engine.unpack"):
            o_status = np.empty(n, dtype=np.int32)
            o_remaining = np.empty(n, dtype=_I64)
            o_reset = np.empty(n, dtype=_I64)
            for piece, arr in zip(self._pieces, arrs):
                _packed, dst_idx, m, _size = piece[:4]
                # Narrow-format pieces carry their own unpacker (uniform
                # batches, bucket_kernel.unpack_uniform_out_host).
                unpack = piece[4] if len(piece) > 4 else unpack_out_host
                if isinstance(dst_idx, list):
                    # Sharded piece: arr is [n_shards, PACKED_OUT_ROWS,
                    # width]; dst_idx/m are per-shard request-index
                    # rows / lane counts.
                    for sh, idxs in enumerate(dst_idx):
                        mm = m[sh]
                        if mm == 0:
                            continue
                        st, rem, rst = unpack_out_host(arr[sh], mm)
                        o_status[idxs] = st
                        o_remaining[idxs] = rem
                        o_reset[idxs] = rst
                else:
                    st, rem, rst = unpack(arr, m)
                    o_status[dst_idx] = st
                    o_remaining[dst_idx] = rem
                    o_reset[dst_idx] = rst
            over = int(np.sum(o_status == int(Status.OVER_LIMIT)))
        with self._engine._lock:
            # Counted at materialization; a dropped PendingColumnar
            # (fire-and-forget caller) does not contribute.
            self._engine.over_limit_total += over
        # limit is echoed from the request (the kernel's limit output is
        # always the request limit).
        self._result = (o_status, self._limit, o_remaining, o_reset)
        self._pieces = ()
        return self._result


def write_through_store(
    store,
    requests: Sequence[RateLimitReq],
    valid_idx: List[int],
    greg_dur: np.ndarray,
    now_ms: int,
    responses: List[Optional[RateLimitResp]],
    expire_of: dict,
) -> None:
    """Store.OnChange per touched key, values derived from the response
    (see gubernator_tpu.store docstring for the leaky precision
    caveat).  Shared by both engines.
    reference: algorithms.go:164-169,266-269.
    """
    from gubernator_tpu.store import CacheItem, LeakyBucketItem, TokenBucketItem

    for i in valid_idx:
        r = requests[i]
        resp = responses[i]
        if resp is None or resp.error:
            continue
        key = r.hash_key()
        greg = bool(int(r.behavior) & Behavior.DURATION_IS_GREGORIAN)
        dur = int(greg_dur[i]) if greg else r.duration
        if int(r.algorithm) == int(Algorithm.TOKEN_BUCKET):
            if int(r.behavior) & Behavior.RESET_REMAINING:
                # reference: algorithms.go:83-97 (remove then recreate).
                store.remove(key)
            value = TokenBucketItem(
                status=int(resp.status),
                limit=resp.limit,
                duration=dur,
                remaining=resp.remaining,
                created_at=now_ms if greg else resp.reset_time - dur,
            )
        else:
            value = LeakyBucketItem(
                limit=resp.limit,
                duration=dur,
                remaining=float(resp.remaining),
                updated_at=now_ms,
                burst=r.burst,
            )
        store.on_change(
            r,
            CacheItem(
                key=key,
                value=value,
                expire_at=int(expire_of[i]),
                algorithm=int(r.algorithm),
            ),
        )


def build_restore_record(
    restores: List[tuple], capacity: int, size: Optional[int] = None
) -> dict:
    """Build SlotRecord columns hydrating store-provided CacheItems
    into fresh slots; `restores` = [(slot, CacheItem)], slots unique.
    Returns the dict of [size] numpy columns (padding lanes carry
    distinct ascending out-of-range slots: capacity + lane).
    reference: the Store.Get read-through of algorithms.go:46-54."""
    from gubernator_tpu.store import LeakyBucketItem, TokenBucketItem, words_from_float

    restores = sorted(restores, key=lambda r: r[0])
    n = len(restores)
    if size is None:
        size = _pad_size(n, floor=16)
    rec = {
        "slot": np.arange(capacity, capacity + size, dtype=np.int64).astype(_I32),
        "algo": np.zeros(size, dtype=_I32),
        "status": np.zeros(size, dtype=_I32),
        "limit": np.zeros(size, dtype=_I64),
        "remaining": np.zeros(size, dtype=_I64),
        "remf_hi": np.zeros(size, dtype=_I32),
        "remf_lo": np.zeros(size, dtype=np.uint32),
        "duration": np.zeros(size, dtype=_I64),
        "t0": np.zeros(size, dtype=_I64),
        "expire_at": np.zeros(size, dtype=_I64),
        "burst": np.zeros(size, dtype=_I64),
        "invalid_at": np.zeros(size, dtype=_I64),
    }
    for lane, (slot, item) in enumerate(restores):
        v = item.value
        rec["slot"][lane] = slot
        rec["expire_at"][lane] = item.expire_at
        rec["invalid_at"][lane] = item.invalid_at
        if isinstance(v, TokenBucketItem):
            rec["algo"][lane] = int(Algorithm.TOKEN_BUCKET)
            rec["status"][lane] = v.status
            rec["limit"][lane] = v.limit
            rec["remaining"][lane] = v.remaining
            rec["duration"][lane] = v.duration
            rec["t0"][lane] = v.created_at
        elif isinstance(v, LeakyBucketItem):
            rec["algo"][lane] = int(Algorithm.LEAKY_BUCKET)
            rec["limit"][lane] = v.limit
            w = (
                v.remaining_words
                if v.remaining_words is not None
                else words_from_float(v.remaining)
            )
            rec["remf_hi"][lane] = w[0]
            rec["remf_lo"][lane] = np.uint32(w[1])
            rec["duration"][lane] = v.duration
            rec["t0"][lane] = v.updated_at
            rec["burst"][lane] = v.burst
    return rec


def require_in_place(verdict, name: str = "fused_step"):
    """`fused_step_ok`'s verdict (on the mesh `mesh_step`'s, named by
    `name`) for `probes`, or on an accelerator the refusal to start on
    its no: a donated step that XLA compiled with
    a state-sized temp would copy the table every dispatch (4.8 GB at
    100 M rows), and there is no second step program to serve instead.
    Both engines construct through here.

    XLA:CPU does say no — it clones six of the twelve columns whatever
    the size, which passes the probe's 1 MiB floor only under 43,691
    rows — and its step then costs O(rows) (0.15 / 2.6 / 20 ms at
    4,096 / 400,000 / 4 M rows in this sandbox).  The CPU backend
    carries tests, rehearsals and `GUBER_PLATFORM=cpu`, not a
    deployment, so there the no is logged and the same program
    serves."""
    if verdict.ok:
        return verdict
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{name} probe said no ({verdict.reason}): the donated "
            "bucket step does not compile in place on this backend, "
            "so the engine does not start"
        )
    log.warning(
        "%s probe said no (%s): XLA:CPU copies part of the "
        "state every dispatch; serving the same step program",
        name, verdict.reason,
    )
    return verdict


class DecisionEngine:
    """Single-device decision engine over `capacity` bucket slots.

    The multi-device variant lives in
    `gubernator_tpu.parallel.sharded_engine`; it shares this host tier.
    """

    def __init__(
        self,
        capacity: int = 50_000,  # reference default cache size (config.go:294)
        *,
        clock: Clock = SYSTEM_CLOCK,
        device: Optional[jax.Device] = None,
        max_kernel_width: int = 8192,
        store=None,  # gubernator_tpu.store.Store (write-through hooks)
    ):
        if not jax.config.jax_enable_x64:
            raise RuntimeError(
                "gubernator_tpu requires jax x64 (timestamps and counters "
                "are int64); do not set GUBERNATOR_TPU_X64=0 when using "
                "the engine"
            )
        # Persisting XLA:CPU executables is unsafe; no-op on TPU (see
        # platform_guard.disable_cpu_persistent_cache).
        from gubernator_tpu.platform_guard import disable_cpu_persistent_cache

        disable_cpu_persistent_cache()
        # Paged device state (GUBER_PAGED; core/paging.py, PERF.md
        # §30): `capacity` becomes the LOGICAL key capacity — the
        # intern table's size — while the device array shrinks to the
        # resident frames.  Everything below this block that says
        # `capacity` means DEVICE capacity: kernel shapes, padding
        # sentinels, pump no-op buffers, and the sweep all keep their
        # dense-plane contracts at the (smaller) resident size, and
        # the host translates logical slots → device rows per batch.
        from gubernator_tpu.config import (
            env_page_size,
            env_paged,
            env_paged_resident,
        )

        self.logical_capacity = capacity
        if env_paged():
            from gubernator_tpu.core.paging import PagePlane

            self.paging: Optional["PagePlane"] = PagePlane(
                capacity, env_page_size(), env_paged_resident()
            )
            capacity = self.paging.device_capacity
        else:
            self.paging = None
        self.capacity = capacity
        self.clock = clock
        self._device = device
        self.max_kernel_width = max_kernel_width
        # Native C++ table when buildable (batch schedule() fast path),
        # Python InternTable otherwise — behaviorally identical
        # (fuzz-tested in tests/test_native_table.py).  Sized at the
        # LOGICAL capacity: key↔slot lives entirely on the host, so in
        # paged mode it grows 10-100x past the device array.
        from gubernator_tpu.core.native import make_intern_table

        self.table = make_intern_table(self.logical_capacity)
        self.store = store
        with jax.default_device(device) if device else nullcontext():
            self._state: BucketState = make_state(capacity)  # guberlint: guarded-by _lock
        # RLock: PumpTicket.fetch may flush from a thread already
        # inside the engine (dataclass-path dispatch fetches inline).
        self._lock = threading.RLock()
        # Next window start for incremental sweep.
        self._sweep_cursor = 0  # guberlint: guarded-by _lock
        # The bucket step is one family of donated XLA programs
        # (ops/bucket_kernel.py); which of them a batch runs follows
        # from the batch's shape.  The in-place probe is a fault
        # detector, not a selector: a step compiled with a state-sized
        # temp would copy the table every dispatch, so on an
        # accelerator a no stops the start (`require_in_place`).  Each
        # probe's verdict and reason is kept in `self.probes`
        # (core/device_info.py serves them).
        import os as _os

        self.probes: dict = {
            "fused_step": require_in_place(fused_step_ok(capacity))
        }
        # Cross-call dispatch batching (core/pump.py): queue packed
        # rounds, run ≤16 of them per dispatch via lax.scan.  Only
        # when the step and the scanned program keep the donated state
        # in place, and only on accelerator backends — the pump amortizes
        # per-dispatch transfer/launch overhead that the in-process
        # CPU backend does not have (GUBER_PUMP=1/0 overrides).
        pump_env = _os.environ.get("GUBER_PUMP", "")
        want_pump = (
            pump_env == "1"
            or (pump_env != "0" and jax.default_backend() != "cpu")
        )
        self._pump: Optional["StepPump"] = None
        if want_pump and self.probes["fused_step"].ok:
            verdict = self.probes["multi_step"] = multi_step_ok(capacity)
            if verdict.ok:
                from gubernator_tpu.core.pump import StepPump

                self._pump = StepPump(self)
            else:
                log.warning(
                    "multi_step probe said no (%s): serving per-round "
                    "dispatch (no step pump)", verdict.reason,
                )
        # Metrics (reference: gubernator.go:59-113 catalog; wired to
        # prometheus in gubernator_tpu.utils.metrics).
        self.requests_total = 0  # guberlint: guarded-by _lock
        self.over_limit_total = 0  # guberlint: guarded-by _lock
        self.batches_total = 0  # guberlint: guarded-by _lock
        self.rounds_total = 0  # guberlint: guarded-by _lock
        # Decision-plane DEVICE DISPATCH counter: every device program
        # the serving path launches (apply step, clears, restores,
        # collapsed/uniform steps, pump scan groups and their device
        # stacks) — the numerator of the dispatches-per-batch gauge the
        # fused plane pins to 1 in steady state (PERF.md §24).
        self.dispatches_total = 0  # guberlint: guarded-by _lock
        # Of those, the step dispatches whose program writes the state
        # by rows (`write_form`): the share of the steps that take the
        # row write on this table.
        self.row_write_dispatches_total = 0  # guberlint: guarded-by _lock
        # Rows restored through load(), either path.
        self.rows_loaded_total = 0  # guberlint: guarded-by _lock
        # Rows of one columnar restore scatter (_load_columns): as wide
        # as the table up to 2^20, so that one program is compiled and
        # `_scatter_hints` keeps its scatter the pass over each column
        # (1.2 ms at 100 M rows, 2^20 rows a pass).
        self.load_width = min(1 << 20, _pad_size(capacity, floor=1024))
        # device.step: the host's ENQUEUE wall of one dispatch (h2d +
        # launch, both staged below) — not device time.
        self.round_duration = DurationStat()
        # The served path's stages (utils/metrics.ENGINE_STAGES).
        # engine.load: a piece of the columnar restore (the sharded
        # engine has it too, beside its router's mesh.route).
        self.stages = engine_stages(extra=("engine.load",))
        # True while the lock's holder serves a section that reads its
        # CPU clock (apply_columnar); only CPU_STAGES, all of them under
        # the lock, act on it.
        self._cpu_section = False  # guberlint: guarded-by _lock
        # Engine-wide d2h transfer batching (core/readback.py): every
        # dispatched output registers a ticket; readers share one
        # stacked transfer instead of paying a device→host read each.
        from gubernator_tpu.core.readback import ReadbackCombiner

        self.readback = ReadbackCombiner()
        self._platform = (
            device if device is not None else jax.devices()[0]
        ).platform
        self._record_write_forms()

    def write_form(self, width: int) -> str:
        """How this engine's step program writes the state at `width`
        lanes (`bucket_kernel._scatter_form`: "pass", "rows" or
        "loop"), read off the two shapes and the platform it serves on."""
        return _scatter_form(self.capacity, width, self._platform)

    def _record_write_forms(self) -> None:
        """The write form at every width of the ladder up to
        `max_kernel_width`, under `probes` (no fault: always ok)."""
        forms, width = [], 64
        while width <= self.max_kernel_width:
            forms.append(f"{width}:{self.write_form(width)}")
            width *= 2
        self.probes["write_form"] = ProbeVerdict(True, " ".join(forms))

    def _stage(self, name: str, work: bool = True) -> stage:
        return stage(
            name, self.stages[name], work,
            # guberlint: ok lock — read for CPU_STAGES only, each of
            # them entered under _lock
            cpu=name in CPU_STAGES and self._cpu_section,
        )

    def _h2d(self, buf):
        """Upload one packed round; a round the pump pre-staged is on
        the device already and passes through."""
        if isinstance(buf, jax.Array):
            return buf
        with self._stage("device.h2d"):
            return jnp.asarray(buf)

    def _step(self, program, pin):  # guberlint: holds _lock
        """One round's donated step program as ONE device.launch: the
        jitted call returning (the enqueue, not the device's run) and
        the donated state's old buffers let go."""
        with self._stage("device.launch"):
            self._state, pout = program(self._state, pin)
            self.dispatches_total += 1
            if self.write_form(pin.shape[-1]) == "rows":
                self.row_write_dispatches_total += 1
        return pout

    # ------------------------------------------------------------------

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        """Apply a batch of rate-limit checks; responses in request order."""
        if now_ms is None:
            now_ms = self.clock.now_ms()
        n = len(requests)
        if n == 0:
            return []

        responses: List[Optional[RateLimitResp]] = [None] * n
        now_dt = None

        # Host-side precompute: Gregorian fields + per-item validation.
        greg_dur = np.zeros(n, dtype=_I64)
        greg_exp = np.zeros(n, dtype=_I64)
        valid_idx: List[int] = []
        for i, r in enumerate(requests):
            if int(r.behavior) & _GREG:
                if now_dt is None:
                    # Derive civil time from now_ms itself — a second
                    # clock read could land in a different calendar
                    # interval than the kernel's `now`.
                    now_dt = dt_from_ms(now_ms)
                try:
                    greg_dur[i] = gregorian_duration(now_dt, r.duration)
                    greg_exp[i] = gregorian_expiration(now_dt, r.duration)
                except GregorianError as e:
                    # Error-in-response, not error-in-RPC
                    # (reference: gubernator.go:264-274).
                    responses[i] = RateLimitResp(error=str(e))
                    continue
            valid_idx.append(i)

        with self._lock:
            self._apply_valid(requests, valid_idx, greg_dur, greg_exp, now_ms, responses)
            self.requests_total += n
            self.batches_total += 1
        return responses  # type: ignore[return-value]

    # guberlint: holds _lock
    def _apply_valid(
        self,
        requests: Sequence[RateLimitReq],
        valid_idx: List[int],
        greg_dur: np.ndarray,
        greg_exp: np.ndarray,
        now_ms: int,
        responses: List[Optional[RateLimitResp]],
    ) -> None:
        if not valid_idx:
            return
        keys = [requests[i].hash_key() for i in valid_idx]

        # Paged mode: a batch's working set must fit the resident
        # frames (unique pages ≤ unique keys).  Oversized batches
        # split into contiguous arrival-order segments processed
        # sequentially — per-slot ordering holds because each
        # segment's responses materialize before the next dispatches.
        if self.paging is not None and len(valid_idx) > self.paging.frames:
            segs = _segments_by_unique_keys(keys, self.paging.frames)
            if len(segs) > 1:
                for lo, hi in segs:
                    self._apply_valid(
                        requests, valid_idx[lo:hi], greg_dur, greg_exp,
                        now_ms, responses,
                    )
                return

        # Split into rounds: the k-th operation on a slot → round k, so
        # each device step touches a slot at most once (see module
        # docstring).  Eviction clears participate in the same per-slot
        # sequence: a clear of slot s must run after the evicted key's
        # last request on s (earlier rounds) and no later than the
        # reusing key's first request (clears run before the round's
        # apply step), so a clear is scheduled at the slot's current
        # sequence number without consuming one.  Store restores (write-
        # through hydration of new keys) run after the clear, before the
        # apply, in that same round.
        rounds: dict[int, List[int]] = {}
        clear_rounds: dict[int, List[int]] = {}
        restore_rounds: dict[int, List[tuple]] = {}
        if self.store is None and hasattr(self.table, "schedule"):
            # Batch fast path: one native call interns the whole batch
            # and assigns rounds + eviction clears.
            slots, rounds_arr, evicted, evict_rounds = self.table.schedule(
                [k.encode() for k in keys], now_ms
            )
            max_round = int(rounds_arr.max()) if len(rounds_arr) else 0
            if max_round == 0:
                rounds[0] = list(range(len(keys)))
            else:
                for j, k in enumerate(rounds_arr.tolist()):
                    rounds.setdefault(k, []).append(j)
            for es, k in zip(evicted.tolist(), evict_rounds.tolist()):
                clear_rounds.setdefault(k, []).append(es)
        else:
            slots = np.empty(len(keys), dtype=_I32)
            seq: dict[int, int] = {}
            for j, key in enumerate(keys):
                evicted_l: List[int] = []
                is_new = not self.table.contains(key)
                slot = self.table.intern(key, now_ms, evicted_l)
                for es in evicted_l:
                    clear_rounds.setdefault(seq.get(es, 0), []).append(es)
                k = seq.get(slot, 0)
                seq[slot] = k + 1
                rounds.setdefault(k, []).append(j)
                slots[j] = slot
                if is_new and self.store is not None:
                    # Read-through (reference: algorithms.go:46-54).
                    item = self.store.get(requests[valid_idx[j]])
                    if item is not None and item.value is not None:
                        restore_rounds.setdefault(k, []).append((slot, item))

        # Paged translation: fault the batch's pages resident, then
        # hand the dispatch machinery DEVICE rows — the step programs
        # see the same dense indexing they always did.  The intern
        # table keeps LOGICAL slots.
        lslots = slots
        if self.paging is not None:
            slots = self.paging.translate(self, slots)

        host_expire = np.zeros(len(valid_idx), dtype=_I64)
        with span(
            "engine.batch", batch=len(valid_idx), rounds=len(rounds)
        ):
            if (
                self.store is None
                and len(rounds) > 1
                and self._collapse_dataclass(
                    requests, valid_idx, slots, greg_dur, greg_exp, now_ms,
                    responses, host_expire, clear_rounds,
                )
            ):
                self.table.set_expiry(lslots, host_expire)
                return
            for k in sorted(rounds):
                members = rounds[k]
                cleared = clear_rounds.get(k)
                if cleared:
                    self._apply_clears(np.asarray(cleared, dtype=_I32))
                restores = restore_rounds.get(k)
                if restores:
                    self._apply_restores(restores)
                # Bound device shapes: chunk wide rounds so one
                # oversized client batch can't force unbounded XLA
                # recompiles.
                for lo in range(0, len(members), self.max_kernel_width):
                    chunk = members[lo : lo + self.max_kernel_width]
                    with span("engine.round", round=k, width=len(chunk)):
                        self._run_round(
                            requests,
                            valid_idx,
                            chunk,
                            slots,
                            greg_dur,
                            greg_exp,
                            now_ms,
                            responses,
                            host_expire,
                        )
                    self.rounds_total += 1

        # Refresh the host TTL mirror for eviction ordering.
        self.table.set_expiry(lslots, host_expire)

        if self.store is not None:
            self._write_through(
                requests, valid_idx, greg_dur, now_ms, responses, host_expire
            )

    def _dispatch(self, buf: np.ndarray, program):  # guberlint: holds _lock
        """One device round: single h2d of the packed buffer, then the
        donated step program; returns the packed output (caller starts
        the async readback)."""
        t0 = _time.monotonic()
        pout = self._step(program, self._h2d(buf))
        self.round_duration.observe(_time.monotonic() - t0)
        return pout

    def _dispatch_collapsed(self, buf: np.ndarray):
        # The collapsed program reads state directly: queued pump
        # rounds must land first (ordering contract, core/pump.py).
        self._flush_pump()
        return self._dispatch(buf, collapsed_step)

    def _dispatch_uniform(self, buf: np.ndarray):
        """Narrow uniform-batch step."""
        return self._dispatch(buf, uniform_step)

    def _dispatch_packed(self, buf: np.ndarray):
        return self._dispatch(buf, fused_step)

    def _flush_pump(self) -> None:
        """Apply queued pump rounds before any OTHER state access (see
        core/pump.py ordering contract).  Caller holds the lock."""
        if self._pump is not None:
            self._pump.flush_locked()

    def _apply_clears(self, cleared: np.ndarray) -> None:  # guberlint: holds _lock
        """Eviction clears: a separate tiny scatter so the apply
        kernel's compiled shapes never depend on eviction pressure.
        `cleared` holds LOGICAL slots in paged mode — resident pages
        clear on device, non-resident ones drop the occupied bit in
        the host page store (no device work, no fault)."""
        if self.paging is not None:
            resident = (
                self.paging.frame_of[cleared >> self.paging.page_shift] >= 0
            )
            cold = cleared[~resident]
            if len(cold):
                self._flush_pump()
                self.paging.clear_host_slots(cold.astype(np.int64))
            cleared = self.paging.resident_rows(
                cleared[resident].astype(np.int64)
            )
            if len(cleared) == 0:
                return
        self._flush_pump()
        # One leaf stage for what a round's evictions cost the host
        # inside the lock: pad, upload and enqueue of the clear (the
        # pump's flush above ran through its own stages).
        with self._stage("engine.evict_clear"):
            csize = _pad_size(len(cleared), floor=16)
            c = np.arange(
                self.capacity, self.capacity + csize, dtype=np.int64
            ).astype(_I32)
            c[: len(cleared)] = cleared
            self._state = self._state._replace(
                meta=clear_occupied(self._state.meta, jnp.asarray(c))
            )
            self.dispatches_total += 1

    def _apply_restores(self, restores: List[tuple]) -> None:  # guberlint: holds _lock
        """Hydrate store-provided bucket values into fresh slots —
        one batched device scatter (see build_restore_record).  Slots
        are LOGICAL in paged mode: rows landing in resident pages
        scatter on device as before; rows whose page is cold pack
        straight into the host page store, so a bulk restore
        (checkpoint load, handoff receive) never faults the whole key
        space through the resident frames just to spill it again."""
        self._flush_pump()
        if self.paging is not None:
            lslots = np.asarray([s for s, _ in restores], dtype=np.int64)
            resident = (
                self.paging.frame_of[lslots >> self.paging.page_shift] >= 0
            )
            cold = [r for r, ok in zip(restores, resident) if not ok]
            if cold:
                self.paging.host_restore(cold)
            hot = [r for r, ok in zip(restores, resident) if ok]
            if not hot:
                return
            dev = self.paging.resident_rows(
                np.asarray([s for s, _ in hot], dtype=np.int64)
            )
            restores = [
                (int(d), item) for d, (_s, item) in zip(dev, hot)
            ]
        rec = build_restore_record(restores, self.capacity)
        self._state = load_slots(
            self._state,
            SlotRecord(**{k: jnp.asarray(a) for k, a in rec.items()}),
        )
        self.dispatches_total += 1

    def _write_through(
        self,
        requests: Sequence[RateLimitReq],
        valid_idx: List[int],
        greg_dur: np.ndarray,
        now_ms: int,
        responses: List[Optional[RateLimitResp]],
        host_expire: np.ndarray,
    ) -> None:
        write_through_store(
            self.store,
            requests,
            valid_idx,
            greg_dur,
            now_ms,
            responses,
            {i: int(host_expire[j]) for j, i in enumerate(valid_idx)},
        )

    # guberlint: holds _lock
    def _run_round(
        self,
        requests: Sequence[RateLimitReq],
        valid_idx: List[int],
        members: List[int],
        slots: np.ndarray,
        greg_dur: np.ndarray,
        greg_exp: np.ndarray,
        now_ms: int,
        responses: List[Optional[RateLimitResp]],
        host_expire: np.ndarray,
    ) -> None:
        """One round of the dataclass path, dispatched through the SAME
        packed single-transfer program as the columnar path (host
        presort by slot, one h2d, one/two kernels, one readback) — the
        old per-column transfers paid the backend's per-op dispatch
        floor 10× per round (PERF.md §2)."""
        from gubernator_tpu.ops.bucket_kernel import unpack_out_host

        m = len(members)
        c_slot = np.empty(m, dtype=_I32)
        c_algo = np.empty(m, dtype=_I32)
        c_beh = np.empty(m, dtype=_I32)
        c_hits = np.empty(m, dtype=_I64)
        c_limit = np.empty(m, dtype=_I64)
        c_dur = np.empty(m, dtype=_I64)
        c_burst = np.empty(m, dtype=_I64)
        c_gdur = np.empty(m, dtype=_I64)
        c_gexp = np.empty(m, dtype=_I64)
        for lane, j in enumerate(members):
            i = valid_idx[j]
            r = requests[i]
            c_slot[lane] = slots[j]
            c_algo[lane] = int(r.algorithm)
            beh = int(r.behavior)
            c_beh[lane] = beh
            c_hits[lane] = r.hits
            c_limit[lane] = r.limit
            c_dur[lane] = r.duration
            c_burst[lane] = r.burst
            c_gdur[lane] = greg_dur[i]
            c_gexp[lane] = greg_exp[i]
            # Host TTL mirror estimate (device value is authoritative).
            if beh & _GREG:
                host_expire[j] = greg_exp[i]
            else:
                host_expire[j] = now_ms + r.duration

        sort_idx = np.argsort(c_slot, kind="stable")
        buf = pack_batch_host(
            _pad_size(m),
            now_ms,
            self.capacity,
            np.ascontiguousarray(c_slot[sort_idx]),
            c_algo[sort_idx],
            c_beh[sort_idx],
            c_hits[sort_idx],
            c_limit[sort_idx],
            c_dur[sort_idx],
            c_burst[sort_idx],
            c_gdur[sort_idx],
            c_gexp[sort_idx],
        )
        if self._pump is not None:
            ticket = self._pump.submit(buf)
        else:
            ticket = self.readback.register(self._dispatch_packed(buf))
        o_status, o_rem, o_reset = unpack_out_host(ticket.fetch(), m)
        over = 0
        for pos, sj in enumerate(sort_idx.tolist()):
            j = members[sj]
            i = valid_idx[j]
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

    # ------------------------------------------------------------------

    # Fixed sweep window: bounds per-call host transfer (one count
    # scalar + freed indices) and compiled shapes regardless of
    # capacity (VERDICT r1 item 4 — the old full-mask readback was
    # ~100MB per sweep at 100M slots).
    SWEEP_WINDOW = 1 << 17

    def sweep(
        self, now_ms: Optional[int] = None, max_windows: Optional[int] = None
    ) -> int:
        """Reclaim slots of expired buckets; returns number freed.

        `max_windows` limits this call to that many SWEEP_WINDOW-sized
        ranges, resuming from a cursor next call — the incremental mode
        for very large capacities; None sweeps everything.
        """
        if now_ms is None:
            now_ms = self.clock.now_ms()

        def release(order, count, start) -> int:
            c = int(count)
            if c:
                freed_slots = np.asarray(order[:c]).astype(np.int64) + start
                if self.paging is not None:
                    # Device rows → logical slots: the intern table
                    # only ever sees the logical space.
                    freed_slots = self.paging.logical_of_device(freed_slots)
                self.table.release_slots(freed_slots)
            return c

        with self._lock:
            # Queued rounds dispatch through their own leaf stages
            # first: engine.sweep never encloses another annotation.
            self._flush_pump()
            with self._stage("engine.sweep") as st:
                freed = windowed_sweep(
                    self, self.capacity, now_ms, max_windows, release
                )
                if self.paging is not None:
                    # Non-resident pages never reach the device sweep;
                    # the host copy tracks their TTLs
                    # (core/paging.sweep_host) so cold expired rows
                    # free WITHOUT faulting in.
                    host_freed = self.paging.sweep_host(now_ms)
                    if len(host_freed):
                        self.table.release_slots(host_freed)
                        freed += len(host_freed)
                if st.span is not None:
                    st.span.set_attribute("freed", freed)
            return freed

    # ------------------------------------------------------------------
    # Columnar fast path: the engine's native request format.
    #
    # The dataclass API above exists for wire compatibility; at high QPS
    # the per-object Python cost dominates the kernel, so batch sources
    # that can produce columns (the bench harness, a native front-end,
    # the GLOBAL hit aggregator) call this instead: keys + numpy columns
    # in, numpy columns out — zero per-item Python in the hot loop.

    def apply_columnar(
        self,
        keys: List[bytes],
        algo: np.ndarray,  # int32 [n]
        behavior: np.ndarray,  # int32 [n]
        hits: np.ndarray,  # int64 [n]
        limit: np.ndarray,  # int64 [n]
        duration: np.ndarray,  # int64 [n]
        burst: np.ndarray,  # int64 [n]
        now_ms: Optional[int] = None,
        want_async: bool = False,
        count_decisions: bool = True,
    ):
        """Vectorized decision path; returns (status, limit, remaining,
        reset_time) int64/int32 numpy arrays in request order — or,
        with want_async=True, a PendingColumnar whose .get() yields
        them, letting the caller overlap the device→host readback of
        this batch with dispatch of the next (double buffering).

        Requires no Store attached (the write-through path needs
        per-item dataclasses) and handles DURATION_IS_GREGORIAN via a
        per-item fallback only for the flagged lanes.

        `count_decisions=False` applies the batch without bumping the
        decision counters — the decision ledger's settle reconciliation
        (core/ledger.py) is device work but not client decisions, and
        counting it would flatter the dispatches-per-decision gauge's
        denominator.
        """
        if self.store is not None:
            raise RuntimeError(
                "apply_columnar does not support a write-through Store; "
                "use get_rate_limits"
            )
        n = len(keys)
        if now_ms is None:
            now_ms = self.clock.now_ms()
        greg_dur = None
        greg_exp = None
        greg_mask = (behavior & int(Behavior.DURATION_IS_GREGORIAN)) != 0
        if greg_mask.any():
            greg_dur = np.zeros(n, dtype=_I64)
            greg_exp = np.zeros(n, dtype=_I64)
            now_dt = dt_from_ms(now_ms)
            for i in np.nonzero(greg_mask)[0]:
                # Invalid intervals surface as status=OVER+error in the
                # dataclass path; columnar callers pre-validate.
                greg_dur[i] = gregorian_duration(now_dt, int(duration[i]))
                greg_exp[i] = gregorian_expiration(now_dt, int(duration[i]))

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
                with span("engine.columnar", batch=n):
                    pending = self._apply_columnar_locked(
                        keys, algo, behavior, hits, limit, duration,
                        burst, greg_dur, greg_exp, greg_mask, now_ms,
                    )
                    if count_decisions:
                        self.requests_total += n
                        self.batches_total += 1
            finally:
                # The serial section: histogram only — it is the parent
                # of the leaf stages that tile it.
                self._cpu_section = False
                cpu = None if c_held is None else _time.thread_time() - c_held
                self.stages["engine.lock_hold"].observe(
                    _time.monotonic() - t_held, cpu
                )
        return pending if want_async else pending.get()

    def _apply_columnar_locked(
        self, keys, algo, behavior, hits, limit, duration, burst,
        greg_dur, greg_exp, greg_mask, now_ms,
    ):
        n = len(keys)
        # Paged mode: segment oversized batches so each segment's
        # working set fits the resident frames (mirrors _apply_valid;
        # pieces from sub-batches re-offset into the caller's lanes).
        if self.paging is not None and n > self.paging.frames:
            with self._stage("engine.intern"):  # a pass over the keys
                key_list = (
                    keys.to_list() if isinstance(keys, PackedKeys) else keys
                )
                segs = _segments_by_unique_keys(key_list, self.paging.frames)
            if len(segs) > 1:
                pieces: List[tuple] = []
                for lo, hi in segs:
                    sub = self._apply_columnar_locked(
                        key_list[lo:hi], algo[lo:hi], behavior[lo:hi],
                        hits[lo:hi], limit[lo:hi], duration[lo:hi],
                        burst[lo:hi],
                        None if greg_dur is None else greg_dur[lo:hi],
                        None if greg_exp is None else greg_exp[lo:hi],
                        greg_mask[lo:hi], now_ms,
                    )
                    for p in sub._pieces:
                        pieces.append((p[0], p[1] + lo) + p[2:])
                return PendingColumnar(self, pieces, limit, n)

        with self._stage("engine.intern"):
            table = self.table
            if isinstance(keys, PackedKeys) and hasattr(
                table, "schedule_packed"
            ):
                slots, rounds_arr, evicted, evict_rounds = (
                    table.schedule_packed(keys.buf, keys.offsets, now_ms)
                )
            elif hasattr(table, "schedule"):
                if isinstance(keys, PackedKeys):
                    keys = keys.to_list()
                slots, rounds_arr, evicted, evict_rounds = table.schedule(
                    keys, now_ms
                )
            else:
                if isinstance(keys, PackedKeys):
                    keys = keys.to_list()
                slots = np.empty(n, dtype=_I32)
                rounds_arr = np.empty(n, dtype=_I32)
                seq: dict[int, int] = {}
                ev_list: List[int] = []
                ev_rounds: List[int] = []
                for j, key in enumerate(keys):
                    cleared: List[int] = []
                    slot = table.intern(key.decode(), now_ms, cleared)
                    for es in cleared:
                        ev_list.append(es)
                        ev_rounds.append(seq.get(es, 0))
                    k = seq.get(slot, 0)
                    seq[slot] = k + 1
                    slots[j] = slot
                    rounds_arr[j] = k
                evicted = np.asarray(ev_list, dtype=_I32)
                evict_rounds = np.asarray(ev_rounds, dtype=_I32)

            if greg_dur is None:
                greg_dur = _ZEROS_CACHE.get(n)
                greg_exp = greg_dur
            max_round = int(rounds_arr.max()) if n else 0

        # Paged translation (see _apply_valid): collapse/dispatch pack
        # DEVICE rows; the intern table keeps LOGICAL slots.  Eviction
        # clears stay logical — _apply_clears owns that split.  Outside
        # engine.intern: a fault flushes the pump and moves pages
        # (device.page_fault, and the dispatch's own leaf stages).
        lslots = slots
        if self.paging is not None:
            slots = self.paging.translate(self, slots)

        pieces: Optional[List[tuple]] = None
        if max_round > 0:
            # Hot-key batches: one dispatch per duplicate would be the
            # worst case (Zipf traffic measured ~1500 rounds/batch);
            # uniform duplicate segments collapse to ONE dispatch with
            # exact sequential semantics (bucket_kernel closed form).
            pieces = self._try_collapse(
                slots, algo, behavior, hits, limit, duration, burst,
                greg_dur, greg_exp, now_ms, evicted, evict_rounds,
            )
        if pieces is None:
            pieces = self._dispatch_rounds(
                slots, rounds_arr, max_round, algo, behavior, hits,
                limit, duration, burst, greg_dur, greg_exp, now_ms,
                evicted, evict_rounds, n,
            )

        with self._stage("engine.set_expiry"):
            expires = np.where(greg_mask, greg_exp, now_ms + duration)
            self.table.set_expiry(lslots, expires.astype(_I64))
            return PendingColumnar(self, pieces, limit, n)

    def _uniform_params(
        self, algo, behavior, hits, limit, duration, burst
    ) -> Optional[tuple]:
        """Gate for the narrow uniform-batch format (bucket_kernel
        UNIFORM_IN_ROWS): one limit config across the batch, 32-bit-
        safe values, no Gregorian.  ~µs of numpy checks buy an 8×
        smaller uplink payload on the transfer-bound backend."""
        if self._pump is None or len(algo) == 0:
            return None
        a0 = int(algo[0])
        b0 = int(behavior[0])
        h0 = int(hits[0])
        l0 = int(limit[0])
        d0 = int(duration[0])
        u0 = int(burst[0])
        # Gregorian needs per-lane fields; RESET_REMAINING responds
        # with reset_time=0 (reference semantics), which the narrow
        # (reset - now) int32 delta cannot represent.
        if b0 & (_GREG | int(Behavior.RESET_REMAINING)):
            return None
        if not (0 <= l0 < 2**31 and 0 <= u0 < 2**31 and 0 < d0 < 2**31):
            return None
        if not -(2**31) < h0 < 2**31:
            return None
        if (
            (algo != a0).any() or (behavior != b0).any()
            or (hits != h0).any() or (limit != l0).any()
            or (duration != d0).any() or (burst != u0).any()
        ):
            return None
        return (a0, b0, h0, l0, d0, u0)

    # guberlint: holds _lock
    def _dispatch_rounds(
        self, slots, rounds_arr, max_round, algo, behavior, hits, limit,
        duration, burst, greg_dur, greg_exp, now_ms, evicted,
        evict_rounds, n,
    ) -> List[tuple]:
        # engine.pack is observed per slice of host work (the plan, a
        # round's gather, a chunk's sort + pack), never around a
        # dispatch: chunk k+1 packs while the device runs chunk k.
        with self._stage("engine.pack"):
            if max_round == 0:
                round_members = [(0, None)]  # None = all lanes, no gather
            else:
                order = np.argsort(rounds_arr, kind="stable")
                sorted_rounds = rounds_arr[order]
                uniq, starts = np.unique(sorted_rounds, return_index=True)
                bounds = list(starts) + [n]
                round_members = [
                    (int(k), order[bounds[i] : bounds[i + 1]])
                    for i, k in enumerate(uniq)
                ]

            clear_by_round: dict[int, List[int]] = {}
            for es, k in zip(evicted.tolist(), evict_rounds.tolist()):
                clear_by_round.setdefault(k, []).append(es)

            # Dispatch: host presorts each chunk by slot (the sort the
            # device kernel would otherwise pay a sorting network for),
            # packs the whole round into ONE int32 buffer (one h2d op
            # on a dispatch-bound backend — see bucket_kernel
            # PACKED_IN_ROWS), runs the donated step program, and
            # starts an async copy of the packed outputs.
            # Materialization happens in PendingColumnar.get(), so the
            # caller can overlap this batch's readback with the next
            # batch's dispatch.
            uni = self._uniform_params(
                algo, behavior, hits, limit, duration, burst
            )
            if uni is not None:
                from gubernator_tpu.ops.bucket_kernel import (
                    pack_uniform_host,
                    unpack_uniform_out_host,
                )

                def unpack_uni(arr, m, _now=now_ms):
                    return unpack_uniform_out_host(arr, m, _now)

            all_cols = (algo, behavior, hits, limit, duration, burst,
                        greg_dur, greg_exp)

        pieces: List[tuple] = []
        for k, members in round_members:
            cleared = clear_by_round.get(k)
            if cleared:
                self._apply_clears(np.asarray(cleared, dtype=_I32))
            if members is None:
                c_slot, cols = slots, all_cols
            else:
                with self._stage("engine.pack"):
                    c_slot = slots[members]
                    cols = tuple(a[members] for a in all_cols)
            m_total = len(c_slot)
            for lo in range(0, m_total, self.max_kernel_width):
                with self._stage("engine.pack"):
                    hi = min(lo + self.max_kernel_width, m_total)
                    m = hi - lo
                    size = _pad_size(m)
                    sort_idx = np.argsort(c_slot[lo:hi], kind="stable")
                    c_sorted = np.ascontiguousarray(
                        c_slot[lo:hi][sort_idx], dtype=_I32
                    )
                    if uni is not None:
                        buf = pack_uniform_host(
                            size, now_ms, self.capacity, c_sorted, *uni
                        )
                    else:
                        buf = pack_batch_host(
                            size, now_ms, self.capacity, c_sorted,
                            *(a[lo:hi][sort_idx] for a in cols),
                        )
                    # Request indices of the sorted lanes, for
                    # unpermuting.
                    if members is None:
                        dst_idx = sort_idx + lo if lo else sort_idx
                    else:
                        dst_idx = members[lo:hi][sort_idx]
                if self._pump is not None:  # the uniform format implies it
                    ticket = self._pump.submit(buf)
                else:
                    ticket = self.readback.register(
                        self._dispatch_packed(buf)
                    )
                self.rounds_total += 1
                if uni is not None:
                    pieces.append((ticket, dst_idx, m, size, unpack_uni))
                else:
                    pieces.append((ticket, dst_idx, m, size))
        return pieces

    # guberlint: holds _lock
    def _collapse_dataclass(
        self,
        requests: Sequence[RateLimitReq],
        valid_idx: List[int],
        slots: np.ndarray,
        greg_dur: np.ndarray,
        greg_exp: np.ndarray,
        now_ms: int,
        responses: List[Optional[RateLimitResp]],
        host_expire: np.ndarray,
        clear_rounds: dict,
    ) -> bool:
        """Hot-key batches on the dataclass path (GLOBAL items, CLI,
        forwarded dataclasses): build columns once and reuse the
        columnar collapse.  Returns False for the rounds fallback."""
        from gubernator_tpu.ops.bucket_kernel import unpack_out_host

        if any(k > 0 for k in clear_rounds):
            return False
        nv = len(valid_idx)
        c_algo = np.empty(nv, dtype=_I32)
        c_beh = np.empty(nv, dtype=_I32)
        c_hits = np.empty(nv, dtype=_I64)
        c_limit = np.empty(nv, dtype=_I64)
        c_dur = np.empty(nv, dtype=_I64)
        c_burst = np.empty(nv, dtype=_I64)
        c_gdur = np.empty(nv, dtype=_I64)
        c_gexp = np.empty(nv, dtype=_I64)
        for j, i in enumerate(valid_idx):
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
            host_expire[j] = greg_exp[i] if beh & _GREG else now_ms + r.duration
        cleared = clear_rounds.get(0, [])
        with span("engine.collapsed", width=nv):
            pieces = self._try_collapse(
                slots, c_algo, c_beh, c_hits, c_limit, c_dur, c_burst,
                c_gdur, c_gexp, now_ms,
                np.asarray(cleared, dtype=_I32),
                np.zeros(len(cleared), dtype=_I32),
            )
        if pieces is None:
            return False
        over = 0
        for pout, dst_idx, m, _size in pieces:
            st, rem, rst = unpack_out_host(pout.fetch(), m)
            for pos, j in enumerate(dst_idx.tolist()):
                i = valid_idx[j]
                s = int(st[pos])
                if s == _OVER_I:
                    over += 1
                responses[i] = RateLimitResp(
                    status=_STATUS_OF[s],
                    limit=int(c_limit[j]),
                    remaining=int(rem[pos]),
                    reset_time=int(rst[pos]),
                )
        self.over_limit_total += over  # rounds_total counted per piece
        return True

    # guberlint: holds _lock
    def _try_collapse(
        self, slots, algo, behavior, hits, limit, duration, burst,
        greg_dur, greg_exp, now_ms, evicted, evict_rounds,
    ) -> Optional[List[tuple]]:
        """Collapse uniform duplicate segments into one dispatch each
        chunk; returns pieces, or None when the batch needs rounds
        (non-uniform duplicate fields, RESET_REMAINING on a duplicate,
        or a mid-batch slot reuse via eviction)."""
        # engine.pack, slice by slice (the gate, then each chunk's
        # pack), never around a dispatch: chunk k+1 packs while the
        # device runs chunk k.
        with self._stage("engine.pack"):
            # Mid-batch eviction reuse (a slot freed after use and handed
            # to ANOTHER key in the same batch) breaks the one-key-per-
            # segment invariant.
            if len(evict_rounds) and int(evict_rounds.max()) > 0:
                return None
            n = len(slots)
            order = np.argsort(slots, kind="stable")  # stable = arrival order
            sorted_slots = slots[order]
            uniq, seg_start, counts = np.unique(
                sorted_slots, return_index=True, return_counts=True
            )
            seg_of = np.repeat(np.arange(len(uniq), dtype=np.int64), counts)
            dup_lane = counts[seg_of] > 1
            cols = (algo, behavior, hits, limit, duration, burst,
                    greg_dur, greg_exp)
            for col in cols:
                cs = col[order]
                if not np.array_equal(
                    cs[dup_lane], cs[seg_start][seg_of][dup_lane]
                ):
                    return None
            beh_sorted = behavior[order]
            reset = (beh_sorted & int(Behavior.RESET_REMAINING)) != 0
            if bool(reset[dup_lane].any()):
                return None
            # Sequential leaky semantics re-clamp remaining to burst on
            # EVERY gather; with negative hits the closed form would skip
            # the intermediate clamps — keep those (rare) on the rounds
            # path.
            if bool(
                (
                    (algo[order] == int(Algorithm.LEAKY_BUCKET))
                    & (hits[order] < 0)
                )[dup_lane].any()
            ):
                return None
            sorted_cols = tuple(col[order] for col in cols)

        # All clears are round 0 here: run them before dispatching.
        if len(evicted):
            self._apply_clears(np.asarray(evicted, dtype=_I32))

        pieces: List[tuple] = []
        for lo in range(0, n, self.max_kernel_width):
            with self._stage("engine.pack"):
                hi = min(lo + self.max_kernel_width, n)
                m = hi - lo
                # Per-chunk segments (a segment split across chunks is
                # fine: the next chunk's first occurrence re-gathers
                # the post-scatter state — still exact).
                c_slots = sorted_slots[lo:hi]
                c_uniq, c_start, c_counts = np.unique(
                    c_slots, return_index=True, return_counts=True
                )
                c_seg_of = np.repeat(
                    np.arange(len(c_uniq), dtype=np.int64), c_counts
                )
                c_pos = np.arange(m, dtype=np.int64) - c_start[c_seg_of]
                size = _pad_size(m)
                buf = pack_collapsed_host(
                    size,
                    now_ms,
                    self.capacity,
                    np.ascontiguousarray(c_uniq, dtype=_I32),
                    c_counts.astype(np.int64),
                    tuple(c[lo:hi][c_start] for c in sorted_cols),
                    c_seg_of.astype(_I32),
                    c_pos.astype(_I32),
                )
            pout = self._dispatch_collapsed(buf)
            self.rounds_total += 1
            pieces.append(
                (self.readback.register(pout), order[lo:hi], m, size)
            )
        return pieces

    # ------------------------------------------------------------------
    # Bulk persistence (reference: store.go:69-78 Loader; the pool-level
    # drivers are gubernator_pool.go:341-531 Load/Store)

    def load(self, loader) -> int:
        """Stream the cache in before serving; returns rows restored.
        A loader that hands over columns (`load_columns`, store.py) is
        restored a chunk at a time; one that has only `load()` — and a
        paged engine, whose restores split by page residency — item by
        item.  Either way the stream's order is the LRU order (first
        row oldest), a key seen twice keeps its last row, and a stream
        longer than the table evicts as served misses do.

        reference: gubernator.go:146-152 → gubernator_pool.go:341-427.
        """
        columns = getattr(loader, "load_columns", None)
        if columns is not None and self.paging is None:
            return self._load_columns(columns())
        count = 0
        batch: List[tuple] = []
        pending_slots: set = set()
        now_ms = self.clock.now_ms()

        def flush():
            nonlocal batch
            if batch:
                with self._stage("engine.load"):
                    self._apply_restores(batch)
                batch = []
                pending_slots.clear()

        with self._lock:
            self._flush_pump()
            for item in loader.load():
                if item.value is None or not item.key:
                    continue
                evicted: List[int] = []
                slot = self.table.intern(item.key, now_ms, evicted)
                # The TTL mirror at once, as the columnar path and
                # upstream's Add have it: an eviction later in the
                # stream then counts this row as unexpired.
                self.table.set_expiry(
                    np.asarray([slot], dtype=_I32),
                    np.asarray([item.expire_at], dtype=_I64),
                )
                # A re-used slot (eviction, or a loader emitting the
                # same key twice) must not appear twice in one restore
                # scatter, and its clear must not run after a pending
                # restore of the same slot — flush first.
                if slot in pending_slots or any(
                    e in pending_slots for e in evicted
                ):
                    flush()
                if evicted:
                    self._apply_clears(np.asarray(evicted, dtype=_I32))
                batch.append((slot, item))
                pending_slots.add(slot)
                count += 1
                if len(batch) >= 4096:
                    flush()
            flush()
            self.rows_loaded_total += count
        return count

    def _load_columns(self, chunks) -> int:
        """The columnar restore: per piece of `load_width` rows one
        bulk insert into the intern table (which writes the TTL mirror
        row by row), and one `load_slots` scatter of that fixed width.
        No clear runs: an evicted slot goes at once to the row that
        evicted it, and a restore writes every column of its row."""
        count = 0
        now_ms = self.clock.now_ms()
        with self._lock:
            self._flush_pump()
            for chunk in chunks:
                for lo in range(0, len(chunk), self.load_width):
                    with self._stage("engine.load"):
                        count += self._load_piece(chunk, lo, now_ms)
        return count

    def _load_piece(self, chunk, lo: int, now_ms: int) -> int:  # guberlint: holds _lock
        hi = min(lo + self.load_width, len(chunk))
        offsets = chunk.key_offsets[lo : hi + 1]
        # The piece's rows of the chunk: a slice while they are all of
        # them in order, an index array from then on.
        rows = slice(lo, hi)
        keyed = offsets[1:] > offsets[:-1]
        if not keyed.all():
            # A row without a key restores nothing; its two equal
            # boundaries fall to one, the buffer stays as it is.
            rows = np.flatnonzero(keyed) + lo
            offsets = np.concatenate((offsets[:1], offsets[1:][keyed]))
        slots = self.table.load_rows(
            chunk.key_buf, offsets, chunk.expire_at[rows], now_ms
        )
        n = len(slots)
        if n > 1 and not (slots[1:] > slots[:-1]).all():
            # The scatter wants its slots ascending and each once: of
            # a slot's rows (a key seen twice, a slot evicted and given
            # away inside the piece) the last one is its state.
            order = np.argsort(slots, kind="stable")
            last = np.ones(n, dtype=bool)
            last[:-1] = slots[order][1:] != slots[order][:-1]
            keep = order[last]
            if isinstance(rows, slice):
                rows = np.arange(lo, hi)
            rows, slots = rows[keep], slots[keep]
        self._restore_rows(slots, lambda name: getattr(chunk, name)[rows])
        self.rows_loaded_total += n
        return n

    def _restore_rows(self, slots: np.ndarray, column) -> None:  # guberlint: holds _lock
        """One `load_slots` scatter of `load_width` lanes: `slots`
        ascending and each once, `column(name)` their values of that
        SlotRecord column; the lanes beyond them are padding."""
        from gubernator_tpu.store import COLUMN_DTYPES

        width, n = self.load_width, len(slots)
        if n == width:  # a full piece goes up as it is
            rec = {"slot": slots}
            for name, dtype in COLUMN_DTYPES.items():
                rec[name] = np.asarray(column(name), dtype=dtype)
        else:
            rec = {"slot": np.arange(
                self.capacity, self.capacity + width, dtype=np.int64
            ).astype(_I32)}
            rec["slot"][:n] = slots
            for name, dtype in COLUMN_DTYPES.items():
                rec[name] = np.zeros(width, dtype=dtype)
                if n:
                    rec[name][:n] = column(name)
        self._state = load_slots(
            self._state,
            SlotRecord(**{k: jnp.asarray(a) for k, a in rec.items()}),
        )
        self.dispatches_total += 1

    def export_items(self):
        """Full-fidelity device→host snapshot as CacheItems.

        reference: gubernator_pool.go:468-531 (Store → Loader.Save).
        """
        from gubernator_tpu.store import CacheItem, LeakyBucketItem, TokenBucketItem

        with self._lock:
            self._flush_pump()
            from gubernator_tpu.ops.bucket_kernel import unpack_state_host

            u = unpack_state_host(self._state)
            slots = np.nonzero(u["occupied"])[0]
            if self.paging is not None:
                lsl = self.paging.logical_of_device(slots.astype(np.int64))
            else:
                lsl = slots
            rows = [
                (u, int(sl), self.table.key_for_slot(int(ls)))
                for sl, ls in zip(slots, lsl)
            ]
            if self.paging is not None:
                # Cold pages export straight from the host copy (bit-
                # identical words) — a full-cache export must never
                # fault the whole key space through resident frames.
                for page in self.paging.nonresident_used_pages():
                    hu = self.paging.host_rows(page)
                    base = page << self.paging.page_shift
                    for r in np.nonzero(hu["occupied"])[0]:
                        rows.append(
                            (hu, int(r),
                             self.table.key_for_slot(base + int(r)))
                        )
        from gubernator_tpu.store import item_from_record

        for u, sl, key in rows:
            if key is None:
                continue
            yield item_from_record(
                key=key,
                algorithm=int(u["algo"][sl]),
                status=int(u["status"][sl]),
                limit=int(u["limit"][sl]),
                remaining=int(u["remaining"][sl]),
                remf_hi=int(u["remf_hi"][sl]),
                remf_lo=int(u["remf_lo"][sl]),
                duration=int(u["duration"][sl]),
                t0=int(u["t0"][sl]),
                expire_at=int(u["expire"][sl]),
                burst=int(u["burst"][sl]),
                invalid_at=int(u["invalid"][sl]),
            )

    def save(self, loader) -> None:
        """Stream the cache out at shutdown (reference: Loader.Save)."""
        loader.save(self.export_items())

    def warmup(self, max_width: int = 1024) -> None:
        """Pre-compile the kernel for every padded batch width up to
        `max_width` (server batches cap at MAX_BATCH_SIZE=1000 → width
        1024) and every eviction-clear width, so no client request pays
        an XLA compile, and hold `max_kernel_width` to it from then on.
        Warmup keys expire after 1ms, a sweep reclaims their slots, and
        metric counters are restored afterwards."""
        # Under the engine lock end-to-end: warmup mutates _state
        # (clear-scatter ladder, pump scans) and restores counters;
        # the RLock keeps the nested get_rate_limits/apply_columnar/
        # sweep calls re-entrant.  Serving traffic that arrives mid-
        # warmup simply queues behind it.
        with self._lock:
            saved = (
                self.requests_total,
                self.batches_total,
                self.rounds_total,
                self.dispatches_total,
                self.row_write_dispatches_total,
                self.table.hits,
                self.table.misses,
                self.table.evictions,
                self.table.unexpired_evictions,
            )
            # Warmup traffic must not reach a write-through Store (it would
            # persist junk __warmup__ keys and pay external round-trips).
            saved_store, self.store = self.store, None
            try:
                now = self.clock.now_ms()
                width = 64
                while width <= max_width:
                    reqs = [
                        RateLimitReq(
                            name="__warmup__",
                            unique_key=str(i),
                            hits=0,
                            limit=1,
                            duration=1,
                        )
                        for i in range(width)
                    ]
                    self.get_rate_limits(reqs, now_ms=now)
                    width *= 2
                # Columnar-kernel ladder: the wire path packs with other
                # pad widths than the dataclass path above (uniform and
                # collapsed programs too) — without this ladder the first
                # served columnar batch pays an XLA compile that can exceed the
                # peer batch timeout ("timeout waiting for batched
                # response").
                width = 64
                while width <= max_width:
                    self.apply_columnar(
                        [b"__warmup___%d" % i for i in range(width)],
                        np.zeros(width, dtype=_I32),
                        np.zeros(width, dtype=_I32),
                        np.zeros(width, dtype=_I64),  # hits=0: report-only
                        np.ones(width, dtype=_I64),
                        np.ones(width, dtype=_I64),
                        np.zeros(width, dtype=_I64),
                        now_ms=now,
                    )
                    # Duplicate keys → the collapsed-segment program (a
                    # separate compile family from the packed step).
                    self.apply_columnar(
                        [b"__warmup__dup" for _ in range(width)],
                        np.zeros(width, dtype=_I32),
                        np.zeros(width, dtype=_I32),
                        np.zeros(width, dtype=_I64),
                        np.ones(width, dtype=_I64),
                        np.ones(width, dtype=_I64),
                        np.zeros(width, dtype=_I64),
                        now_ms=now,
                    )
                    width *= 2
                # Clear-scatter ladder (no-op out-of-range slots).
                csize = 16
                while csize <= max_width:
                    dummy = jnp.asarray(
                        np.arange(self.capacity, self.capacity + csize, dtype=np.int64).astype(_I32)
                    )
                    self._state = self._state._replace(
                        meta=clear_occupied(self._state.meta, dummy)
                    )
                    csize *= 2
                # The columnar restore's one program (_load_columns),
                # every lane padding: a daemon with a Loader restores
                # right after this warm-up.
                if self.paging is None:
                    self._restore_rows(np.zeros(0, dtype=_I32), None)
                # Readback-combiner stack ladder: concurrent/pipelined
                # callers share one stacked d2h transfer; precompile the
                # stack programs per output width (core/readback.py).
                from gubernator_tpu.ops.bucket_kernel import PACKED_OUT_ROWS

                width = 64
                while width <= max_width:
                    self.readback.warmup_stacks((PACKED_OUT_ROWS, width), jnp.int32)
                    width *= 2
                # Step-pump scan ladder: fused multi-round programs per
                # width (core/pump.py) — the serving path under concurrent
                # load groups cross-call rounds into these.
                if self._pump is not None:
                    width = 64
                    while width <= max_width:
                        self._pump.warmup(width)
                        width *= 2
                self.sweep(now_ms=now + 2)
                (
                    self.requests_total,
                    self.batches_total,
                    self.rounds_total,
                    self.dispatches_total,
                    self.row_write_dispatches_total,
                    *saved_table,
                ) = saved
                t = self.table
                if hasattr(t, "discount_stats"):
                    # The native table mirrors cumulative C++ counters on
                    # every schedule(); plain attribute restore would be
                    # overwritten by the next mirror, so register discounts
                    # instead.
                    t.discount_stats(*(
                        now - then for now, then in zip(
                            (t.hits, t.misses, t.evictions,
                             t.unexpired_evictions), saved_table)
                    ))
                else:
                    (t.hits, t.misses, t.evictions,
                     t.unexpired_evictions) = saved_table
                # Nothing served is wider than what was just compiled:
                # a wider batch (the native front's window merges RPCs
                # up to 8,192 rows) is chunked, chunk k+1 packing while
                # the device runs chunk k — compiled on demand at 100 M
                # rows it cost a client its deadline (chip, PR 32).
                self.max_kernel_width = min(self.max_kernel_width, max_width)
                self._record_write_forms()
            finally:
                # Exception-safety: a failed warmup (backend or
                # compile error) must not leave persistence disabled.
                self.store = saved_store

    def cache_size(self) -> int:
        return len(self.table)

    def close(self) -> None:
        pass


