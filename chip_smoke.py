"""On-chip smoke test: both halves of the main path on a TPU.

One chip (the default):

1. warpsim — the paper grid at its real size (15 benchmarks x the 6
   ``machines.paper_suite()`` machines, seed 0: 90 cells in 15 trace
   families) on ``engine="pallas"``, once in-process through
   ``api.Session`` and once through a sweep daemon served on a thread of
   this process via ``ServiceBackend``. Every record must equal an
   ``engine="native"`` run bit for bit.
2. serving — ``launch.serve.BatchedServer`` answers 8 requests on 4 slots
   with tinyllama-1.1b at its full published width (bf16, random weights
   from the seed). Request 0's prefill logits and first decode-step
   logits are checked against an f32 full-sequence forward pass.

``--chips 4`` runs the two multi-chip paths instead, and nothing else:

a. the SW+ expert-parallel MoE layer on a 2x2 ("data", "model") mesh at
   qwen2-moe-a2.7b's layer width, against the dense oracle on one device;
b. ``launch.train`` on tinyllama-1.1b at full width with
   ``--model-parallel 2``, its first-step loss against an f32 forward
   loss of the same batch on one device.

Any failed check exits non-zero. Without a TPU (for example under
``JAX_PLATFORMS=cpu``) the script exits non-zero before any phase. The
last line of a passing run is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

  python chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Tolerances of the model-side checks against their f32 references.
# bf16 weights and activations through 22 layers: the logits' error is
# measured relative to the reference's largest magnitude.
SERVE_LOGIT_RTOL = 5e-2
TRAIN_LOSS_RTOL = 1e-2
EP_RTOL = 1e-3


class CompileClock:
    """Seconds jax spends tracing, lowering and compiling (or fetching
    from the persistent cache), summed over every thread."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.total = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, *args, **kwargs):
        if event in self.EVENTS:
            with self._lock:
                self.total += duration


class Phase:
    """Wall and compile seconds of one phase."""

    def __init__(self, clock: CompileClock):
        self.clock = clock

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clock.total
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.clock.total - self.c0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# warpsim
# ---------------------------------------------------------------------------


def _record_bits(rec) -> tuple:
    """A RunRecord as exact values: floats by their hex form."""
    res = dataclasses.asdict(rec.result)
    return (rec.machine, rec.bench, rec.seed, rec.n_threads) + tuple(
        (k, v.hex() if isinstance(v, float) else v)
        for k, v in sorted(res.items()))


def _differing(got, want) -> int:
    check(len(got.records) == len(want.records), "record counts differ")
    return sum(_record_bits(a) != _record_bits(b)
               for a, b in zip(got.records, want.records))


def warpsim_phase(study, clock: CompileClock, tmp: str) -> None:
    """The study in-process and served, each against the native engine."""
    from repro.core.warpsim import _native, _pallas, api
    from repro.core.warpsim.service import SweepClient, SweepService, serve

    n_cells = len(study.cells())
    n_families = len(study.benches) * len(study.seeds)
    check(_native.available(),
          f"native C core unavailable ({_native.status()['error']}): "
          "no host reference")
    ref = api.Session(cache_dir=os.path.join(tmp, "native")).run(
        dataclasses.replace(study, engine="native"))
    check(ref.stats["simulated"] == n_cells, "native run hit a warm cache")

    launches0 = _pallas.launch_count()
    with Phase(clock) as ph:
        dev = api.Session(cache_dir=os.path.join(tmp, "inprocess")).run(study)
    launches = _pallas.launch_count() - launches0
    diff = _differing(dev, ref)
    print(f"warpsim in-process: cells_simulated={dev.stats['simulated']} "
          f"trace_families={dev.stats['trace_families']} "
          f"family_launches={launches} compile_s={ph.compile:.3f} "
          f"run_s={ph.wall - ph.compile:.3f} wall_s={ph.wall:.3f} "
          f"records_differing_from_native={diff}", flush=True)
    check(dev.stats["simulated"] == n_cells, "in-process run hit a cache")
    check(launches == dev.stats["family_launches"] == n_families,
          f"{launches} family launches for {n_families} families")
    check(diff == 0, f"{diff} in-process records differ from native")

    svc = SweepService(os.path.join(tmp, "served"), engine="pallas",
                       persist_traces=False)
    httpd = serve(svc)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        url = "http://%s:%d" % httpd.server_address[:2]
        health = SweepClient(url, timeout=600.0).healthz()
        check(health["ok"] and health["engine"] == "pallas",
              f"daemon healthz: {health}")
        launches0 = _pallas.launch_count()
        with Phase(clock) as ph:
            served = api.Session(backend=api.ServiceBackend(
                url=url, timeout=1100.0)).run(study)
        launches = _pallas.launch_count() - launches0
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.join(30)
    diff = _differing(served, ref)
    print(f"warpsim served: cells_simulated={served.stats['simulated']} "
          f"trace_families={served.stats['trace_families']} "
          f"family_launches={launches} compile_s={ph.compile:.3f} "
          f"(summed over daemon threads) wall_s={ph.wall:.3f} "
          f"records_differing_from_native={diff}", flush=True)
    check(served.stats["simulated"] == n_cells, "served run hit a cache")
    check(launches == served.stats["family_launches"] == n_families,
          f"{launches} served family launches for {n_families} families")
    check(diff == 0, f"{diff} served records differ from native")


# ---------------------------------------------------------------------------
# model side
# ---------------------------------------------------------------------------


def _f32_logits(cfg, params, tokens):
    """Full-sequence f32 forward pass: (S, V) logits for one sequence."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib

    cfg32 = dataclasses.replace(cfg, dtype="float32", remat="none")

    @jax.jit
    def fwd(params, tokens):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = model_lib.embed_inputs(p32, cfg32, {"tokens": tokens})
        hid, _ = model_lib.forward_hidden(p32, cfg32, x,
                                          jnp.arange(tokens.shape[1]))
        return model_lib.logits_fn(p32, cfg32, hid)[0]

    with jax.default_matmul_precision("highest"):
        return fwd(params, tokens)


def serve_phase(cfg, clock: CompileClock, seed: int, n_requests: int = 8,
                slots: int = 4, max_new: int = 16, max_len: int = 64) -> None:
    """BatchedServer answers every request; request 0 matches f32."""
    import jax
    import numpy as np

    from repro.launch import serve as serve_lib
    from repro.models import model as model_lib

    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    reqs = [serve_lib.Request(
        i, rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 32)))
        .astype(np.int32), max_new, keep_logits=(i == 0))
        for i in range(n_requests)]
    with Phase(clock) as ph:
        stats = serve_lib.BatchedServer(cfg, params, slots, max_len).run(reqs)
    answered = sum(len(r.generated) == max_new for r in reqs)
    r0 = reqs[0]
    n = len(r0.prompt)
    tokens = np.concatenate([r0.prompt, r0.generated[:1]])[None]
    want = np.asarray(_f32_logits(cfg, params, tokens))[n - 1:n + 1,
                                                        :cfg.vocab_size]
    got = np.stack(r0.logits[:2])[:, :cfg.vocab_size]
    scale = float(np.abs(want).max())
    err = np.abs(got - want).max(axis=1) / scale
    print(f"serve {cfg.name}: requests={stats['requests']} "
          f"answered={answered} slots={slots} new_tokens="
          f"{stats['total_new_tokens']} decode_steps={stats['decode_steps']} "
          f"compile_s={ph.compile:.3f} wall_s={ph.wall:.3f}", flush=True)
    print(f"serve {cfg.name}: request 0 (prompt {n} tokens) vs f32 forward: "
          f"prefill max|d|/max|ref|={err[0]:.3e} first-decode "
          f"max|d|/max|ref|={err[1]:.3e} (tolerance {SERVE_LOGIT_RTOL:g}); "
          f"argmax agrees: {bool((got.argmax(1) == want.argmax(1)).all())}",
          flush=True)
    check(bool(np.isfinite(got).all()), "non-finite serving logits")
    check(answered == n_requests, f"{answered}/{n_requests} answered")
    check(float(err.max()) <= SERVE_LOGIT_RTOL,
          f"serving logits off the f32 reference by {err.max():.3e}")


def ep_phase(cfg, mesh, clock: CompileClock, seed: int,
             batch: int, seq: int) -> None:
    """SW+ expert-parallel MoE layer on `mesh` vs the one-device oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import granularity
    from repro.models import mlp as mlp_mod, moe as moe_mod

    dt = jnp.dtype(cfg.dtype)
    k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(lambda k: moe_mod.moe_init(k, cfg, dt))(k_p)
    x = jax.random.normal(k_x, (batch, seq, cfg.d_model), dt)

    dev0 = jax.devices()[0]
    with jax.default_matmul_precision("highest"):
        @jax.jit
        def oracle(p, x):
            flat = x.reshape(-1, cfg.d_model)
            y, _ = moe_mod.dispatch_dense_oracle(p, flat, cfg)
            return y + mlp_mod.mlp(p["shared"], flat, cfg)

        want = np.asarray(oracle(jax.device_put(params, dev0),
                                 jax.device_put(x, dev0)))

        ep_specs = {"router": P(None, None), "w1": P("model", None, None),
                    "w3": P("model", None, None),
                    "w2": P("model", None, None),
                    "shared": jax.tree.map(lambda _: P(), params["shared"])}
        p_sh = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), ep_specs,
            is_leaf=lambda s: isinstance(s, P)))
        x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        granularity.set_mesh(mesh, ("data",))
        try:
            with Phase(clock) as ph:
                step = jax.jit(lambda p, x: moe_mod.moe_layer(p, x, cfg)[0])
                compiled = step.lower(p_sh, x_sh).compile()
                y = compiled(p_sh, x_sh)
                y.block_until_ready()
        finally:
            granularity.set_mesh(None)
    got = np.asarray(y).reshape(-1, cfg.d_model)
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / scale
    in_sh = compiled.input_shardings[0]
    w1_sh = in_sh[0]["w1"]
    print(f"ep {cfg.name}: mesh={dict(mesh.shape)} tokens={batch * seq} "
          f"d_model={cfg.d_model} experts={cfg.moe_experts}->"
          f"{cfg.moe_experts_eff} top_k={cfg.moe_top_k} shared="
          f"{cfg.moe_shared} ff={cfg.moe_d_ff} compile_s={ph.compile:.3f} "
          f"wall_s={ph.wall:.3f}", flush=True)
    print(f"ep {cfg.name}: compiled w1 sharding {w1_sh.spec} over "
          f"{len(w1_sh.device_set)} devices, w1 shard "
          f"{p_sh['w1'].addressable_shards[0].data.shape}; output "
          f"{y.sharding.spec} on devices "
          f"{sorted(d.id for d in y.sharding.device_set)}; "
          f"max|d|/max|oracle|={err:.3e} (tolerance {EP_RTOL:g})", flush=True)
    check(len(y.sharding.device_set) == mesh.size, "EP output not sharded")
    check(bool(np.isfinite(got).all()), "non-finite EP output")
    check(err <= EP_RTOL, f"EP layer off the oracle by {err:.3e}")


def train_phase(arch: str, smoke: bool, clock: CompileClock, seed: int,
                steps: int, batch: int, seq_len: int,
                model_parallel: int) -> None:
    """launch.train on the mesh; first-step loss vs one-device f32 loss."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticCorpus
    from repro.launch import train as train_lib
    from repro.models import model as model_lib

    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
            "--seq-len", str(seq_len), "--model-parallel",
            str(model_parallel), "--seed", str(seed), "--log-every", "1"]
    with Phase(clock) as ph:
        out = train_lib.main(argv + (["--smoke"] if smoke else []))
    cfg = get_config(arch, smoke=smoke)
    cfg32 = dataclasses.replace(cfg, dtype="float32", remat="none")
    data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=seq_len, global_batch=batch,
                                      seed=seed))
    dev0 = jax.devices()[0]

    @jax.jit
    def ref_loss(key, batch):
        params = model_lib.init_params(key, cfg)
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return model_lib.train_loss(p32, cfg32, batch)[0]

    with jax.default_matmul_precision("highest"):
        want = float(ref_loss(jax.device_put(jax.random.PRNGKey(seed), dev0),
                              jax.device_put(data.batch_at(0), dev0)))
    got = out["first_loss"]
    rel = abs(got - want) / abs(want)
    placement = out["param_bytes_per_device"]
    print(f"train {cfg.name}: mesh=({len(jax.devices()) // model_parallel}, "
          f"{model_parallel}) batch={batch} seq={seq_len} steps={steps} "
          f"losses={[round(v, 5) for v in out['losses']]} "
          f"compile_s={ph.compile:.3f} wall_s={ph.wall:.3f}", flush=True)
    print(f"train {cfg.name}: param bytes per device {placement}; first "
          f"loss {got:.6f} vs one-device f32 {want:.6f} (|d|/ref "
          f"{rel:.3e}, tolerance {TRAIN_LOSS_RTOL:g})", flush=True)
    check(len(placement) == len(jax.devices()),
          f"parameters on {len(placement)} of {len(jax.devices())} devices")
    check(all(v == v and abs(v) < 1e9 for v in out["losses"]),
          "non-finite training loss")
    check(rel <= TRAIN_LOSS_RTOL, f"first loss off the f32 loss by {rel:.3e}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: warpsim + serving; 4: EP layer + sharded "
                         "trainer only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro import compat

    cache_dir = compat.init_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax's default backend is {dev.platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but jax sees {len(devices)} devices")
    print(f"chip_smoke: {len(devices)} x {dev.device_kind}, compile cache "
          f"{cache_dir}", flush=True)
    clock = CompileClock(jax)

    from repro.configs import get_config

    if args.chips == 1:
        from repro.core.warpsim import api, machines, trace
        study = api.Study(benches=tuple(trace.BENCHMARKS),
                          machines=machines.paper_suite(),
                          seeds=(args.seed,), engine="pallas")
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
            warpsim_phase(study, clock, tmp)
        serve_phase(get_config("tinyllama-1.1b"), clock, args.seed)
    else:
        mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2),
                    ("data", "model"))
        ep_cfg = dataclasses.replace(
            get_config("qwen2-moe-a2.7b"), dtype="float32",
            moe_dispatch="sw_plus_ep", moe_capacity_factor=3.0)
        ep_phase(ep_cfg, mesh, clock, args.seed, batch=8, seq=256)
        train_phase("tinyllama-1.1b", False, clock, args.seed, steps=3,
                    batch=8, seq_len=512, model_parallel=2)
    print(f"chip_smoke: total compile_s={clock.total:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
