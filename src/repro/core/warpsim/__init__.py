"""Warp-level SIMT timing model — faithful reproduction of
*Investigating Warp Size Impact in GPUs* (Lashgar, Baniasadi, Khonsari 2012).

Public API:
    api.Session / api.Study / api.StudyResult / api.{InProcessBackend,
    ServiceBackend, QueueBackend}   <- the facade; start here
    MachineConfig, machines.{baseline,sw_plus,lw_plus,paper_suite}
    trace.get_workload / trace.BENCHMARKS
    runner.run_one / run_suite / suite_summary   (run_suite: deprecated
    nested-dict shim over api)
    sweep.SweepSpec / sweep.ResultCache / sweep.run_sweep /
    sweep.run_sweep_with_stats   (the low-level engine under api)
    service.SweepService / service.SweepClient / service.ResilientClient /
    service.from_env
    work_queue.WorkQueue / work_queue.run_worker
    faults.FaultPlan / faults.ServiceError / faults.ServiceUnavailable

Timing engines (``simulate(..., engine=...)`` — all bit-identical):

    ============= ===================================================
    engine        what it is
    ============= ===================================================
    auto          native when the C core compiled, else fast —
                  never pallas (device engine is strictly opt-in)
    native        compiled C scheduling loop (~25x event)
    fast          flat-CSR numpy/heapq loop (always available)
    fast_nested   previous-generation fast path, benchmark baseline
    pallas        JAX device core; in-process sweeps and the
                  daemon's studies batch a whole trace family (all
                  expansion keys x machine variants of one
                  ThreadTrace) into ONE launch (GET /cell is a
                  one-unit launch); runs fast only under
                  WARPSIM_PALLAS=0 (device failures raise)
    event         reference event loop (the model's ground truth)
    ============= ===================================================

Environment variables (the full table; every read goes through
``repro.core.warpsim.envcfg``, which owns each name, default, and doc —
the ``env-registry`` rule of ``repro.core.warpsim.lint`` rejects raw
``os.environ`` reads, and ``tests/test_lint.py`` keeps this list in sync
with the registry):

    ====================== ==============================================
    variable               meaning (default)
    ====================== ==============================================
    WARPSIM_BACKEND        force the Session backend: inprocess |
                           service | queue (unset: prefer a live daemon)
    WARPSIM_SERVICE_URL    single daemon URL -> plain SweepClient
    WARPSIM_SERVICE_URLS   comma-separated fleet -> ResilientClient
    WARPSIM_PEERS          comma-separated mesh peers (disjoint roots)
    WARPSIM_SELF_URL       this daemon's own peer-visible URL
    WARPSIM_REPLICATION    copies per cell/job across the mesh (2)
    WARPSIM_FAULTS         chaos plan; grammar + points in ``faults``
    WARPSIM_NATIVE         C core kill switch: 0|no|off -> pure Python
                           engines (on; re-read per call)
    WARPSIM_NATIVE_DIR     build dir for the compiled C core (per-user
                           tmpdir; refused when not owner-writable-only)
    WARPSIM_PALLAS         device engine kill switch: 0|no|off -> flat
                           CSR engines (on; re-read per call)
    WARPSIM_OBS            observability kill switch: 0|no|off -> span
                           recording, stage histograms and trace header
                           propagation become near-no-ops (on; re-read
                           per call; counters keep counting)
    WARPSIM_OBS_RING       span ring-buffer capacity per daemon/process
                           behind ``GET /debug/trace`` (2048)
    WARPSIM_OBS_SAMPLE     trace sampling rate in [0,1] (1.0); a
                           deterministic hash of the trace id, never RNG
    ====================== ==============================================

Static invariants: ``python -m repro.core.warpsim.lint`` (CI job
``invariant-lint``) enforces jax containment behind ``repro.compat``,
typed ``ServiceError`` HTTP boundaries, ``# guarded-by:`` lock
discipline on module state, determinism of the cache-key/timing
modules, the ``faults.KNOWN_POINTS`` fault-point registry, and the env
registry above. See the ``lint`` module docstring for the rule table
and the suppression syntax.

Serving runbook (the daemon fleet; full details in ROADMAP.md):

    WARPSIM_SERVICE_URLS   comma-separated daemon URLs; clients built by
                           ``service.from_env`` / ``api.Session.from_env``
                           become a ``ResilientClient``: bounded retries of
                           transient failures (5xx / no response) with
                           capped exponential backoff + seeded jitter,
                           immediate failover between endpoints, and a
                           per-endpoint circuit breaker re-admitted only by
                           a passing ``/healthz`` probe. Knobs are
                           constructor args (``max_retries``,
                           ``backoff_base``/``backoff_cap``,
                           ``breaker_threshold``/``breaker_cooldown``,
                           ``attempt_timeout``); counters surface as the
                           ``"client"`` section of ``stats()``.
    WARPSIM_SERVICE_URL    single daemon, plain ``SweepClient`` (legacy).
    WARPSIM_BACKEND        forces the Session backend. Degradation matrix:
                           *unforced* + every endpoint dead -> warn once,
                           run in-process (records identical — cells are
                           deterministic); *forced* service/queue + dead ->
                           raise (RuntimeError; ValueError when no URL env
                           is set at all). Mid-study daemon death with >=2
                           URLS -> invisible to callers (retry + failover;
                           the shared cache root means completed cells are
                           never re-simulated). 4xx responses never retry.
    WARPSIM_PEERS          comma-separated peer URLs: daemons federate
                           into a mesh over *disjoint* cache roots (no
                           shared filesystem). Rendezvous hashing over
                           the cell key picks each cell's owner; a local
                           miss read-throughs to the owner (``GET
                           /peer/cell``) before simulating; completed
                           cells are pushed to WARPSIM_REPLICATION
                           members (``POST /peer/replicate``, default 2)
                           so one daemon + its disk can vanish without
                           losing coverage; queue-job snapshots are
                           replicated/adopted the same way (``/peer/job``)
                           so workers survive their enqueuing daemon.
                           Needs WARPSIM_SELF_URL (this daemon's own
                           peer-visible URL) or ``--advertise-url``.
                           Degradation matrix: owner dead/partitioned ->
                           ask replicas cache-only, then simulate locally
                           (records bit-identical; cost is <= replication
                           duplicate sims); peer draining -> its 503
                           counts as unreachable, requester simulates;
                           key skew across versions -> 400, requester
                           simulates. ``stats()["mesh"]`` has membership
                           + forward/replication/fallback counters.
    WARPSIM_FAULTS         deterministic fault injection for chaos tests,
                           e.g. ``server/study:error=503,times=2;
                           service.cell:kill,after=5;seed=7`` — see
                           ``faults`` module docstring for the grammar
                           (mesh paths: ``peer.forward``,
                           ``peer.replicate``).
    POST /admin/drain      graceful shutdown: stop leasing queue chunks,
                           refuse new cell/study/sweep work with 503,
                           finish in-flight cells, persist queue jobs.
                           ``healthz()["draining"]`` flips true and probe
                           re-admission skips draining daemons.
    GET /metrics           Prometheus text exposition over the daemon's
                           ``warpsim.obs`` registry — the same counters
                           ``/stats`` serves as the legacy dict, plus
                           ``warpsim_stage_seconds{stage=...}`` latency
                           histograms and in-flight gauges. Stages:
                           ``trace_build``, ``aggregate``, ``engine``;
                           the device engine's ``pallas_pack`` (packing
                           a launch), ``pallas_dispatch`` (the jit call)
                           and its device holds, ``device_inflight``
                           (hold time no earlier hold covered: sums to
                           an upper bound on device busy time) and
                           ``device_queued`` (the rest); ``cache_get``,
                           ``cache_put``, ``peer_forward``,
                           ``replicate``, ``worker.lease``/``renew``/
                           ``complete``; ``server/<path>`` per handled
                           request (``server/cell``, ``server/study``).
                           In a process that called
                           ``repro.compat.init_compile_cache()`` every
                           stage, span and device hold also appears in
                           profiler traces as ``warpsim.<stage>`` (the
                           hold as ``warpsim.device``), on the device's
                           clock.
    GET /debug/trace       span ring dump: ``?id=<trace>`` returns that
                           trace's spans (bounded ring, WARPSIM_OBS_RING
                           spans, default 2048 — oldest evicted); without
                           ``id``, per-trace summaries. One study = one
                           trace across clients, daemons, peer forwards,
                           replication pushes and queue workers (ids ride
                           the ``X-Warpsim-Op`` header); merge the
                           fleet's dumps to reconstruct which daemon
                           simulated/cached/forwarded each cell. Overhead
                           is a clock pair + one ring append per span —
                           negligible next to a cell simulation; set
                           WARPSIM_OBS=0 to reduce hooks to near-no-ops.

Workers (``work_queue.run_worker``) retry transient lease/renew/complete
failures with backoff, abandon chunks on lost leases (lease expiry
requeues them), and rely on idempotent completes — a lost complete ack
costs a recompute, never duplicate or wrong data. A worker given the
fleet (comma-separated ``--url``, ``$WARPSIM_SERVICE_URLS``, or a
``ResilientClient``) rotates endpoints on failure *and* on a definite
"unknown job" — a mesh sibling adopts the job from its replicas — so it
survives its enqueuing daemon dying.
"""

from repro.core.warpsim.config import MachineConfig
from repro.core.warpsim import api, machines, runner, sweep, trace
from repro.core.warpsim.api import (
    Session, Study, StudyResult,
)
from repro.core.warpsim.divergence import (
    WarpStream, expand_stream, expand_workload, simd_efficiency,
)
from repro.core.warpsim.faults import (
    FaultPlan, ServiceError, ServiceUnavailable,
)
from repro.core.warpsim.sweep import (
    ResultCache, SweepSpec, expansion_key, run_sweep, run_sweep_with_stats,
)
from repro.core.warpsim.timing import SimResult, simulate

# `service` and `work_queue` are deliberately NOT imported eagerly: both
# are `python -m`-runnable daemons, and importing them here would make
# runpy warn about double-import on startup. `from repro.core.warpsim
# import service` still works (plain submodule import).

__all__ = [
    "MachineConfig", "api", "machines", "runner", "sweep", "trace",
    "Session", "Study", "StudyResult",
    "FaultPlan", "ServiceError", "ServiceUnavailable",
    "WarpStream", "expand_stream", "expand_workload", "simd_efficiency",
    "SimResult", "simulate",
    "ResultCache", "SweepSpec", "expansion_key", "run_sweep",
    "run_sweep_with_stats",
]
