"""JAX trace-family timing core (``engine="pallas"``).

One device launch simulates an entire *trace family*: every (expansion key,
machine variant) pair derived from one :class:`ThreadTrace`. Each pair is a
"unit" — its CSR :class:`WarpStream` columns plus the variant's machine
scalars — and all units of a launch are padded to shared power-of-two
shapes, stacked on a leading axis and run under one ``jax.vmap`` inside one
``jax.jit`` call. The per-block machine mapping (memory controller, L1 set
index) is computed in the same jit; the scheduling recurrence itself —
inherently sequential in simulated time — is a ``lax.while_loop`` over the
CSR op columns, with the ready-warp min-heap recast as a masked ``argmin``
over the per-warp ready times (first-minimum index == heapq's lowest-warp-id
tie-break).

**Number format.** The reference engines compute simulated time in IEEE-754
doubles. XLA:TPU has no f64 unit: it lowers f64 to a pair of f32s, which
is neither IEEE nor bit-identical. So the device program holds no floats at
all. Every time value is the int64 *bit pattern* of its (non-negative)
double. For non-negative doubles the bit patterns order exactly like the
values, so ``max``/``min``/comparisons/``argmin`` run on the integers
unchanged; the only arithmetic the recurrence needs is addition of two
non-negative doubles, done by :func:`_f64_add` — an exact round-to-nearest-
even add on the bit patterns. The one product in the model (store service
occupancy ``svc_unit * (max(nbytes, 32) / 64.0)``) is precomputed on the
host in numpy doubles. Integer ops are exact on every backend, so the
device replays the reference engine's arithmetic bit for bit on CPU and
TPU alike. 64-bit integer types are scoped to the launch with the
thread-local ``jax.enable_x64(True)``.

The same decision sequence as the reference event loop is replayed —
argmin pop order, LRU eviction by unique touch tick, pending-line fill
minimum, SW+ merge window. The SW+ outstanding table becomes a dense
``[n_sms, n_slots]`` array, a block's slot numbering it among the distinct
blocks its own SM's warps touch (exact: a merge only ever matches within
one SM, and the dict's >4096-entry prune only drops entries that can never
merge again, so *any* exact map is equivalent). The L1 tags hold the same
slots. The L1 and SW+ tables are flat vectors, so that the compiler keeps
one layout for them through every gather and scatter. The units of a
launch step in lockstep: each while-loop step pops one op in every unit
not yet done, and one inner loop serves that step's block lookups and
store writes, as many trips as the step's largest op in any unit; a unit
with less to do masks its writes. The golden + hypothesis tests in
``tests/test_golden.py`` assert ``pallas == native == fast == event`` on
every field.

Gating: ``WARPSIM_PALLAS=0`` (re-read on every call, so a live daemon can
be disabled without restart) makes :func:`available` return False and the
entry points return None; callers then run the flat-CSR engines. That
kill switch is the only fallback: a failed import, compile or launch
raises with the underlying message. ``engine="auto"`` never selects
pallas — on CPU hosts the XLA loop is far slower than the C core; the
engine exists for accelerator-resident grids and must be asked for.

:data:`LAUNCHES` counts completed family launches; the sweep layer and the
bench-smoke CI assert on it (a family must cost one launch, not N cells).
Each launch also counts its loop steps (the outer loop's steps plus the
inner loop's trips, as the device ran them) and the bytes of one unit's
loop-carried state in its bucket: the recent launches' in the module
(:func:`loop_steps`, :func:`state_bytes`), every launch's in the ambient
obs domain's ``warpsim_pallas_loop_steps_total`` counter and
``warpsim_pallas_state_bytes`` gauge (the largest seen).
"""

from __future__ import annotations

import collections
import functools
import threading
import warnings
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.warpsim import envcfg
from repro.core.warpsim import obs as obs_mod

# Completed device launches (one per simulated family batch), for the
# one-launch-per-family assertions in tests and bench smoke. Daemon
# threads launch concurrently, so increments take the lock.
LAUNCHES = 0
_LAUNCH_LOCK = threading.Lock()
# (loop steps, state bytes) of the most recent launches, newest last.
_RECENT = collections.deque(maxlen=4096)  # guarded-by: _LAUNCH_LOCK

_modules_cache = None       # (jax, jnp, lax) once imported
# The name of the vmapped axis over a launch's units.
_UNITS = "units"
_import_attempted = False
_import_error: Optional[str] = None
_probe_result: Optional[bool] = None
_warned = False

# Bit patterns of the doubles the device program needs.
_F64_INF = 0x7FF0000000000000
_MANT_MASK = (1 << 52) - 1
_HIDDEN_BIT = 1 << 52


def _env_disabled() -> bool:
    """Kill switch, re-read per call (live daemons honor flips)."""
    return not envcfg.enabled("WARPSIM_PALLAS")


def _modules():
    """Import jax lazily; cache the result (None => unavailable)."""
    global _modules_cache, _import_attempted, _import_error
    if _env_disabled():
        return None
    if _import_attempted:
        return _modules_cache
    _import_attempted = True
    try:
        import jax
        import jax.numpy as jnp
        from jax import lax

        _modules_cache = (jax, jnp, lax)
    except Exception as e:  # jax missing / broken jaxlib
        _import_error = f"{e.__class__.__name__}: {e}"
        _modules_cache = None
    return _modules_cache


def _require_modules():
    """The jax modules, or raise why they are missing (never degrade)."""
    mods = _modules()
    if mods is None:
        raise RuntimeError(
            f"warpsim pallas engine: jax is unavailable ({_import_error})")
    return mods


def _warn_disabled() -> None:
    global _warned
    if _warned:
        return
    _warned = True
    warnings.warn(
        "warpsim pallas engine disabled by WARPSIM_PALLAS; running the "
        "flat-CSR engines instead", RuntimeWarning, stacklevel=3)


def available() -> bool:
    """True iff jax is importable and ``WARPSIM_PALLAS`` is not off.

    Cheap by design (no trace/compile); the first real launch pays the jit
    cost. ``engine="auto"`` must not consult this — pallas is opt-in.
    """
    return _modules() is not None


def launch_count() -> int:
    return LAUNCHES


def _recent(last: int) -> Optional[List[Tuple[int, int]]]:
    """The `last` launches' (loop steps, state bytes); None when fewer
    are remembered."""
    with _LAUNCH_LOCK:
        if last > len(_RECENT):
            return None
        return list(_RECENT)[len(_RECENT) - last:]


def loop_steps(last: int) -> Optional[int]:
    """Loop steps of the `last` launches (None when fewer are
    remembered): each launch's outer steps plus inner trips, counted on
    the device, which set how long it lasts."""
    recent = _recent(last)
    return None if recent is None else sum(n for n, _ in recent)


def state_bytes(last: int) -> Optional[int]:
    """Bytes of one unit's loop-carried state in a launch's bucket, the
    largest of the `last` launches (None when fewer are remembered)."""
    recent = _recent(last)
    return None if recent is None else max((b for _, b in recent), default=0)


def status(probe: bool = False) -> dict:
    """Operator-facing engine report (the sweep service's ``/healthz``).

    ``enabled`` re-reads ``WARPSIM_PALLAS`` at call time. With
    ``probe=True`` a one-op family is actually simulated, so the report
    states whether the device path is live rather than merely importable;
    a failed probe leaves the compiler's message in ``error``.
    """
    global _probe_result
    enabled = not _env_disabled()
    importable = enabled and _modules() is not None
    if probe and importable and _probe_result is None:
        _probe_result = _self_probe()
    ready = importable and (_probe_result is not False)
    return {
        "enabled": enabled,
        "importable": importable,
        "probed": _probe_result,
        "error": _import_error,
        "launches": LAUNCHES,
        "engine": "pallas" if (enabled and ready) else "unavailable",
    }


def _self_probe() -> bool:
    """Simulate a trivial 1-warp stream end-to-end through the launch."""
    global _import_error
    try:
        cols = dict(
            n_warps=1,
            op_start=np.array([0, 2], dtype=np.int64),
            issue=np.array([1, 1], dtype=np.int64),
            kind=np.array([0, 1], dtype=np.int8),
            blk_off=np.array([0, 0], dtype=np.int64),
            blk_len=np.array([0, 1], dtype=np.int64),
            blocks=np.array([3], dtype=np.int64),
            nbytes=np.array([64], dtype=np.int64),
        )
        scal = dict(num_sms=1, num_mem_ctrls=1, n_sets=2, ways=2,
                    ideal=True, hit_lat=1.0, depth=4.0, dram_lat=100.0,
                    svc_unit=2.0)
        out = _launch_units([(cols, scal)], count_launch=False)
        cycles = float(out[0][0])
        return bool(np.isfinite(cycles) and cycles > 0.0)
    except Exception as e:
        # Reported, not swallowed: /healthz shows the message and the
        # engine as unavailable; cells asked for pallas still raise.
        _import_error = f"probe failed: {e.__class__.__name__}: {e}"
        return False


# ---------------------------------------------------------------------------
# Device program
# ---------------------------------------------------------------------------


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — bounds jit retraces."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def _f64_add(jnp, a, b):
    """``a + b`` for int64 bit patterns of finite non-negative doubles.

    Exact IEEE-754 round-to-nearest-even, subnormals included, overflow to
    +inf: align the smaller significand with three extra bits (guard,
    round, sticky), add, renormalize by at most one bit, round. Writing
    the result as ``((e - 1) << 52) + significand`` lets a significand
    carry (hidden bit, or rounding up to 2**53) step the exponent for free.
    """
    hi = jnp.maximum(a, b)
    lo = jnp.minimum(a, b)
    e_hi = hi >> 52
    e_lo = lo >> 52
    m_hi = ((hi & _MANT_MASK) | jnp.where(e_hi > 0, _HIDDEN_BIT, 0)) << 3
    m_lo = ((lo & _MANT_MASK) | jnp.where(e_lo > 0, _HIDDEN_BIT, 0)) << 3
    e_hi = jnp.maximum(e_hi, 1)         # subnormals share exponent 1
    shift = jnp.minimum(e_hi - jnp.maximum(e_lo, 1), 60)
    sticky = (m_lo & ((jnp.ones_like(m_lo) << shift) - 1)) != 0
    s = m_hi + ((m_lo >> shift) | sticky)
    carry = s >> 56                     # 0 or 1: sum reached 2**56
    s = (s >> carry) | (s & carry)
    m = s >> 3
    rest = s & 7
    m = m + ((rest > 4) | ((rest == 4) & ((m & 1) == 1)))
    return jnp.minimum(((e_hi + carry - 1) << 52) + m, _F64_INF)


@functools.lru_cache(maxsize=64)
def _get_launch(n_sms_pad: int, nctrl_pad: int, n_sets_pad: int,
                ways_pad: int, n_slots_pad: int):
    """Build the jitted family function for one state-dimension bucket.

    Array-shape buckets (warps / ops / blocks / units) are handled by jit's
    own shape-keyed cache; the L1 / DRAM / outstanding state dimensions are
    python ints baked into the trace, so they key this cache. The program
    is integer-only (see the module docstring), so one build serves every
    backend; the caller lowers it for whichever device it targets.
    """
    jax, jnp, lax = _require_modules()
    i64 = jnp.int64
    INF = _F64_INF
    add = functools.partial(_f64_add, jnp)

    # ---- Scheduling recurrence for one unit ------------------------------

    def _simulate_one(cols):
        next0 = cols["next0"]
        op_end = cols["end"]
        sm_of = cols["sm_of"]
        issue_col = cols["issue"]
        kind_col = cols["kind"]
        off_col = cols["off"]
        len_col = cols["len"]
        slot_col = cols["slot"]
        ssvc_col = cols["ssvc"]
        # Per-block machine mapping: memory controller and L1 set index.
        ctrl_col = cols["blocks"] % cols["nctrl"][0]
        si_col = cols["blocks"] % cols["nsets"][0]
        ideal = cols["ideal"][0]
        hit_lat = cols["hit_lat"][0]
        depth = cols["depth"][0]
        dram_lat = cols["dram_lat"][0]
        svc_unit = cols["svc"][0]
        ways = cols["ways"][0]

        way_idx = jnp.arange(ways_pad, dtype=i64)
        way_mask = way_idx < ways
        tick_inf = jnp.iinfo(i64).max

        ready0 = jnp.where(next0 < op_end, 0, INF).astype(i64)
        state0 = (
            ready0,
            next0,
            jnp.zeros((n_sms_pad,), i64),                       # issue_free
            jnp.zeros((nctrl_pad,), i64),                       # ctrl_free
            # L1 tags, ticks and fills, flat over [sm, set, way].
            jnp.full((n_sms_pad * n_sets_pad * ways_pad,), -1, i64),
            jnp.zeros((n_sms_pad * n_sets_pad * ways_pad,), i64),
            jnp.zeros((n_sms_pad * n_sets_pad * ways_pad,), i64),
            jnp.zeros((n_sms_pad,), i64),                       # tick ctr
            # Outstanding SW+ completions, flat over [sm, slot]; -1 sorts
            # below every time.
            jnp.full((n_sms_pad * n_slots_pad,), -1, i64),
            jnp.zeros((), i64),                                 # offchip
            jnp.zeros((), i64),                                 # merged
            jnp.zeros((), i64),                                 # l1 hits
            jnp.zeros((), i64),                 # outer steps + inner trips
        )

        # The loops' trip counts are the largest over the launch's units
        # (`pmax` over the vmapped axis), so that vmap keeps them scalar:
        # a per-unit trip count would make it select the whole carried
        # state, L1 and SW+ tables included, after every step. A unit
        # with nothing to do in a step masks its writes instead.
        def cond(st):
            return lax.pmax(jnp.any(st[0] < INF).astype(jnp.int32),
                            _UNITS) > 0

        def body(st):
            (ready, next_idx, issue_free, ctrl_free, tags, ticks, fills,
             tickc, outst, off_n, mrg_n, hit_n, steps) = st
            # Heap pop: first minimum == lowest warp id on ready-time ties,
            # exactly heapq's (time, warp) lexicographic order.
            w = jnp.argmin(ready)
            ready_t = ready[w]
            active = ready_t < INF          # false once this unit is done
            sm = sm_of[w]
            i = next_idx[w]
            t_start = jnp.maximum(ready_t, issue_free[sm])
            t_acc = add(t_start, issue_col[i])
            issue_free = issue_free.at[sm].set(
                jnp.where(active, t_acc, issue_free[sm]))
            o = off_col[i]
            n_blk = len_col[i]
            is_load = active & (kind_col[i] == 1)
            is_store = active & (kind_col[i] == 2)

            # One loop serves a load's L1 lookups and a store's writes.
            def blk(j, c):
                (done, ctrl_free, tags, ticks, fills, tick, outst,
                 off_n, mrg_n, hit_n) = c
                in_op = j < n_blk
                ld = is_load & in_op
                bi = o + j
                b_slot = slot_col[bi]
                b_ctrl = ctrl_col[bi]
                b_si = si_col[bi]
                # L1 lookup (pending lines visible with fill time);
                # every lookup is one LRU touch tick.
                tick = tick + ld.astype(i64)
                line = (sm * n_sets_pad + b_si) * ways_pad
                # Rows are read by index gather: a vmapped dynamic_slice
                # lowers on XLA:TPU to a serial loop over the units.
                row_idx = line + way_idx
                row = tags[row_idx]
                match = (row == b_slot) & way_mask
                present = jnp.any(match)
                widx = line + jnp.argmax(match)
                fill = fills[widx]
                ticks = ticks.at[widx].set(
                    jnp.where(ld & present, tick, ticks[widx]))
                is_hit = ld & present & (fill <= t_acc)
                oidx = sm * n_slots_pad + b_slot
                out = outst[oidx]
                is_merge = ld & (~is_hit) & ideal & (out > t_acc)
                do_dram = ld & (~is_hit) & (~is_merge)
                # DRAM request (full 64 B read transaction), or a store's
                # write of its bytes.
                cf = ctrl_free[b_ctrl]
                start = jnp.maximum(cf, t_acc)
                completion = add(add(start, dram_lat), svc_unit)
                ctrl_free = ctrl_free.at[b_ctrl].set(jnp.where(
                    do_dram, add(start, svc_unit),
                    jnp.where(is_store & in_op, add(start, ssvc_col[bi]),
                              cf)))
                # L1 fill / pending-line allocation.
                tick = tick + do_dram.astype(i64)
                valid = (row != -1) & way_mask
                empties = (~valid) & way_mask
                has_empty = jnp.any(empties)
                tick_row = ticks[row_idx]
                victim = jnp.argmin(
                    jnp.where(valid, tick_row, tick_inf))  # LRU
                ins_way = line + jnp.where(
                    has_empty, jnp.argmax(empties), victim)
                upd_way = jnp.where(present, widx, ins_way)
                tags = tags.at[ins_way].set(
                    jnp.where(do_dram & (~present), b_slot, tags[ins_way]))
                ticks = ticks.at[upd_way].set(
                    jnp.where(do_dram, tick, ticks[upd_way]))
                new_fill = jnp.where(
                    present, jnp.minimum(fill, completion), completion)
                fills = fills.at[upd_way].set(
                    jnp.where(do_dram, new_fill, fills[upd_way]))
                outst = outst.at[oidx].set(
                    jnp.where(do_dram & ideal, completion, out))
                off_n = off_n + do_dram.astype(i64)
                mrg_n = mrg_n + is_merge.astype(i64)
                hit_n = hit_n + is_hit.astype(i64)
                done = jnp.where(is_merge, jnp.maximum(done, out), done)
                done = jnp.where(do_dram,
                                 jnp.maximum(done, completion), done)
                return (done, ctrl_free, tags, ticks, fills, tick,
                        outst, off_n, mrg_n, hit_n)

            trips = lax.pmax(jnp.where(is_load | is_store, n_blk, 0), _UNITS)
            (done, ctrl_free, tags, ticks, fills, tick, outst,
             off_n, mrg_n, hit_n) = lax.fori_loop(
                0, trips, blk,
                (add(t_acc, hit_lat), ctrl_free, tags, ticks, fills,
                 tickc[sm], outst, off_n, mrg_n, hit_n))
            tickc = tickc.at[sm].set(tick)
            off_n = off_n + jnp.where(is_store, n_blk, 0)
            warp_ready = jnp.where(kind_col[i] == 0, add(t_acc, depth), done)

            ni = i + 1
            ready = ready.at[w].set(jnp.where(
                active, jnp.where(ni < op_end[w], warp_ready, INF),
                ready[w]))
            next_idx = next_idx.at[w].set(jnp.where(active, ni, i))
            return (ready, next_idx, issue_free, ctrl_free, tags, ticks,
                    fills, tickc, outst, off_n, mrg_n, hit_n,
                    steps + 1 + trips)

        final = lax.while_loop(cond, body, state0)
        issue_free = final[2]
        return (jnp.max(issue_free), final[9], final[10], final[11],
                final[12])

    return jax.jit(jax.vmap(_simulate_one, axis_name=_UNITS))


# ---------------------------------------------------------------------------
# Host marshalling
# ---------------------------------------------------------------------------


def _stream_cols(stream) -> dict:
    """Numpy CSR columns of a WarpStream (the native core's input layout)."""
    return dict(
        n_warps=stream.n_warps,
        op_start=np.asarray(stream.op_start, dtype=np.int64),
        issue=np.asarray(stream.issue, dtype=np.int64),
        kind=np.asarray(stream.kind, dtype=np.int8),
        blk_off=np.asarray(stream.blk_off, dtype=np.int64),
        blk_len=np.asarray(stream.blk_len, dtype=np.int64),
        blocks=np.asarray(stream.blocks, dtype=np.int64),
        nbytes=np.asarray(stream.nbytes, dtype=np.int64),
    )


def _cfg_scalars(cfg) -> dict:
    return dict(
        num_sms=cfg.num_sms,
        num_mem_ctrls=cfg.num_mem_ctrls,
        n_sets=cfg.l1_size_bytes // (cfg.transaction_bytes * cfg.l1_ways),
        ways=cfg.l1_ways,
        ideal=bool(cfg.ideal_coalescing),
        hit_lat=float(cfg.l1_hit_latency),
        depth=float(cfg.pipeline_depth),
        dram_lat=float(cfg.dram_latency_cycles),
        svc_unit=float(cfg.dram_cycles_per_transaction),
    )


def _bits(x) -> np.ndarray:
    """int64 bit patterns of float64 values (the device's time format)."""
    return np.asarray(x, dtype=np.float64).view(np.int64)


def _sm_of(n_warps: int, n_sms: int) -> np.ndarray:
    """The SM of each warp: contiguous equal shares, in warp order."""
    wids = np.arange(n_warps, dtype=np.int64)
    return np.minimum(wids * n_sms // max(n_warps, 1), n_sms - 1)


def _pow4(n: int) -> int:
    """Smallest power of four >= max(n, 1)."""
    p = 1
    while p < n:
        p *= 4
    return p


def _sm_slots(cols: dict, n_sms: int) -> Tuple[np.ndarray, int, int]:
    """Each block's SW+ slot: its rank among the distinct blocks that its
    own SM's warps touch (the ops of a warp own the contiguous block
    slices ``blk_off:blk_off + blk_len``). Returns the slot column, the
    most slots any SM needs and the stream's distinct blocks."""
    blocks = cols["blocks"]
    if not len(blocks):
        return np.zeros(0, dtype=np.int64), 1, 1
    op_sm = np.repeat(_sm_of(cols["n_warps"], n_sms),
                      np.diff(cols["op_start"]))
    lens = cols["blk_len"]
    owned = np.repeat(cols["blk_off"] - np.cumsum(lens) + lens, lens)
    blk_sm = np.empty(len(blocks), dtype=np.int64)
    blk_sm[owned + np.arange(len(owned))] = np.repeat(op_sm, lens)
    uniq, dense = np.unique(blocks, return_inverse=True)
    pairs, pair_id = np.unique(blk_sm * len(uniq) + dense,
                               return_inverse=True)
    # Pairs sort SM-major: an SM's slots count from its first pair.
    pair_sm = pairs // len(uniq)
    first = np.searchsorted(pair_sm, np.arange(n_sms))
    slots = pair_id.astype(np.int64) - first[blk_sm]
    return slots, int(np.bincount(pair_sm).max()), len(uniq)


def _unit_state_bytes(dims: tuple, w_pad: int) -> int:
    """Bytes of one unit's loop-carried state in bucket `dims`: ready and
    next per warp; issue frees and tick counters per SM; controller
    frees; L1 tags, ticks and fills; the SW+ outstanding table; three
    counters. Every element is 8 bytes."""
    n_sms, nctrl, n_sets, ways, n_slots = dims
    return 8 * (2 * w_pad + 2 * n_sms + nctrl + 3 * n_sms * n_sets * ways
                + n_sms * n_slots + 3)


def pack_units(units: Sequence[Tuple[dict, dict]]) -> Tuple[tuple, dict]:
    """Pad and stack units = [(stream cols, machine scalars)].

    Returns ``(state_dims, stacked)``: the ``_get_launch`` bucket key and
    the dict of ``[u_pad, ...]`` int arrays one launch consumes. Times
    are float64 bit patterns; the store service occupancy is computed
    here with the host's expression, ``svc_unit * (max(nbytes, 32) /
    64.0)``.
    """
    n_units = len(units)
    u_pad = _pow2(n_units)
    w_pad = _pow2(max(c["n_warps"] for c, _ in units))
    ops_pad = _pow2(max(len(c["issue"]) for c, _ in units))
    blk_pad = _pow2(max(len(c["blocks"]) for c, _ in units))
    dims = (_pow2(max(s["num_sms"] for _, s in units)),
            _pow2(max(s["num_mem_ctrls"] for _, s in units)),
            _pow2(max(s["n_sets"] for _, s in units)),
            _pow2(max(s["ways"] for _, s in units)))

    # SW+ outstanding table: dense over each SM's own distinct blocks.
    # Cache the remap per stream object and SM count — variants share
    # their expansion. Its width pads to a power of four, never past the
    # power of two of the distinct blocks (what one table over every SM
    # would need): a benchmark's per-SM count wanders across seeds (BFS
    # at 2 SMs, 1543 to 2271), and the coarser ladder keeps its seeds in
    # one compiled program.
    slot_cache: dict = {}

    def slots_of(cols, n_sms):
        key = (id(cols["blocks"]), n_sms)
        hit = slot_cache.get(key)
        if hit is None:
            hit = slot_cache[key] = _sm_slots(cols, n_sms)
        return hit

    counts = [slots_of(c, s["num_sms"])[1:] for c, s in units]
    dims += (min(_pow4(max(n for n, _ in counts)),
                 _pow2(max(d for _, d in counts))),)

    def stack(width, fill=0):
        return np.full((u_pad, width), fill, dtype=np.int64)

    st = {k: stack(w_pad) for k in ("next0", "end", "sm_of")}
    st.update({k: stack(ops_pad) for k in ("issue", "off", "len")})
    st["kind"] = np.zeros((u_pad, ops_pad), dtype=np.int32)
    st.update({k: stack(blk_pad) for k in ("blocks", "slot", "ssvc")})
    st["ideal"] = np.zeros((u_pad, 1), dtype=bool)
    st.update({k: stack(1) for k in ("hit_lat", "depth", "dram_lat", "svc")})
    # Padding units have no warps; 1 keeps their block modulo defined.
    st.update({k: stack(1, fill=1) for k in ("ways", "nctrl", "nsets")})

    for u, (cols, scal) in enumerate(units):
        nw = cols["n_warps"]
        n_sms = scal["num_sms"]
        st["next0"][u, :nw] = cols["op_start"][:nw]
        st["end"][u, :nw] = cols["op_start"][1:nw + 1]
        st["sm_of"][u, :nw] = _sm_of(nw, n_sms)
        no = len(cols["issue"])
        st["issue"][u, :no] = _bits(cols["issue"])
        st["kind"][u, :no] = cols["kind"]
        st["off"][u, :no] = cols["blk_off"]
        st["len"][u, :no] = cols["blk_len"]
        nb = len(cols["blocks"])
        st["blocks"][u, :nb] = cols["blocks"]
        st["slot"][u, :nb] = slots_of(cols, n_sms)[0]
        # Minimum 32 B burst, exactly the host expression.
        st["ssvc"][u, :nb] = _bits(
            scal["svc_unit"] * (np.maximum(cols["nbytes"], 32) / 64.0))
        st["ideal"][u, 0] = scal["ideal"]
        for k in ("hit_lat", "depth", "dram_lat"):
            st[k][u, 0] = _bits(scal[k])
        st["svc"][u, 0] = _bits(scal["svc_unit"])
        st["ways"][u, 0] = scal["ways"]
        st["nctrl"][u, 0] = scal["num_mem_ctrls"]
        st["nsets"][u, 0] = scal["n_sets"]
    return dims, st


def _launch_units(units: Sequence[Tuple[dict, dict]],
                  count_launch: bool = True) -> List[Tuple]:
    """Pack and simulate units = [(stream cols, machine scalars)].

    One jit call per invocation — the family-launch unit the sweep layer
    and CI assert on. Returns ``(raw_cycles, offchip, merged, l1_hits)``
    per unit, in order. Compile and launch errors propagate. Observed as
    the ``pallas_pack`` stage (packing and the program lookup), one
    ``device`` occupancy hold (``device_inflight`` / ``device_queued``)
    and, inside it, the ``pallas_dispatch`` stage (the jit call alone).
    A counted launch adds its loop steps and state bytes to the module's
    recent launches and to the ambient obs domain's metrics.
    """
    global LAUNCHES
    jax, _jnp, _lax = _require_modules()
    with obs_mod.stage("pallas_pack"):
        dims, stacked = pack_units(units)
        launch = _get_launch(*dims)
    # The hold runs from dispatch until the results are on the host, so
    # its in-flight time bounds the device's busy time from above.
    with obs_mod.occupancy("device", units=len(units)), jax.enable_x64(True):
        with obs_mod.stage("pallas_dispatch"):
            out = launch(stacked)
        cycles, offchip, merged, hits, steps = jax.device_get(out)
    if count_launch:
        steps = int(np.max(steps))
        state = _unit_state_bytes(dims, stacked["next0"].shape[1])
        registry = obs_mod.current_obs().registry
        with _LAUNCH_LOCK:
            LAUNCHES += 1
            _RECENT.append((steps, state))
            registry.counter(
                "warpsim_pallas_loop_steps_total",
                "device loop steps: each launch's outer steps plus inner "
                "trips").inc(steps)
            gauge = registry.gauge(
                "warpsim_pallas_state_bytes",
                "largest loop-carried state of one unit of a launch, bytes")
            gauge.set(max(gauge.value, state))
    cycles = np.asarray(cycles, dtype=np.int64).view(np.float64)
    return [(float(cycles[u]), int(offchip[u]), int(merged[u]),
             int(hits[u])) for u in range(len(units))]


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def run_scheduling_loop(n_warps: int, op_start, issue, kind, blk_off,
                        blk_len, blocks, nbytes, cfg):
    """Single-cell device run; mirrors ``_native.run_scheduling_loop``.

    Returns ``(raw_cycles, offchip, merged, l1_hits)``, or None when
    ``WARPSIM_PALLAS`` is off (callers then run the flat-CSR engine). A
    missing jax or a failed compile/launch raises.
    """
    if _env_disabled():
        _warn_disabled()
        return None
    cols = dict(
        n_warps=int(n_warps),
        op_start=np.asarray(op_start, dtype=np.int64),
        issue=np.asarray(issue, dtype=np.int64),
        kind=np.asarray(kind, dtype=np.int8),
        blk_off=np.asarray(blk_off, dtype=np.int64),
        blk_len=np.asarray(blk_len, dtype=np.int64),
        blocks=np.asarray(blocks, dtype=np.int64),
        nbytes=np.asarray(nbytes, dtype=np.int64),
    )
    return _launch_units([(cols, _cfg_scalars(cfg))])[0]


def run_family(pairs):
    """Simulate a trace family in ONE device launch.

    ``pairs`` is ``[(WarpStream, MachineConfig), ...]`` — every expansion
    key × machine variant of one ThreadTrace (streams may repeat across
    variants that share an expansion). Returns a list of
    ``(raw_cycles, offchip, merged, l1_hits)`` in order, or None when
    ``WARPSIM_PALLAS`` is off. A missing jax or a failed compile/launch
    raises.
    """
    if not pairs:
        return []
    if _env_disabled():
        _warn_disabled()
        return None
    col_cache: dict = {}
    units = []
    for stream, cfg in pairs:
        cols = col_cache.get(id(stream))
        if cols is None:
            cols = col_cache[id(stream)] = _stream_cols(stream)
        units.append((cols, _cfg_scalars(cfg)))
    return _launch_units(units)
