"""Serving driver: batched decode with slot-based continuous batching.

A fixed pool of `--slots` decode slots runs one fused ``decode_step`` per
iteration. Finished or empty slots are refilled from the request queue
(continuous batching): each refill prefills the new prompt and splices its
KV/state cache, its cache positions and its write cursor into the slot.
The cache keeps one position per row, so ragged prompts decode
independently.

CPU-scale demo:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --requests 12 --slots 4 --max-new 24
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs import get_config, list_archs
from repro.models import model as model_lib


class Request:
    """One prompt. With `keep_logits` the server also records the fp32
    logits of each of its steps (prefill first) in `logits`."""

    def __init__(self, rid: int, prompt: np.ndarray, max_new: int,
                 keep_logits: bool = False):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.generated: List[int] = []
        self.done = False
        self.keep_logits = keep_logits
        self.logits: List[np.ndarray] = []


def _splice_cache(pool_cache, req_cache, slot: int):
    """Copy a single-sequence prefill cache into batch slot `slot`.

    Layer-stacked state is (L, B, ...); the per-row cursor leaves
    (``positions`` (B, Sc), ``index`` (B,)) are batch-first.
    """
    def splice(pool, single):
        if pool.ndim >= 3:
            return pool.at[:, slot].set(single[:, 0])
        return pool.at[slot].set(single[0])
    return jax.tree.map(splice, pool_cache, req_cache)


class BatchedServer:
    """Slot-based continuous batching around prefill/decode_step."""

    def __init__(self, cfg, params, slots: int, max_len: int):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.active: List[Optional[Request]] = [None] * slots
        self.cache = model_lib.init_decode_cache(cfg, slots, max_len)
        self._decode = jax.jit(
            lambda p, t, c: model_lib.decode_step(p, self.cfg, t, c))
        self._prefill = jax.jit(
            lambda p, b: model_lib.prefill(p, self.cfg, b, self.max_len))

    def _admit(self, req: Request, slot: int) -> int:
        logits, rcache = self._prefill(
            self.params, {"tokens": jnp.asarray(req.prompt[None, :])})
        self.cache = _splice_cache(self.cache, rcache, slot)
        self.active[slot] = req
        if req.keep_logits:
            req.logits.append(np.asarray(logits[0]))
        return int(jnp.argmax(logits[0]))

    def run(self, requests: List[Request]) -> dict:
        queue = list(requests)
        next_tokens = np.zeros(self.slots, dtype=np.int32)
        t0 = time.time()
        steps = 0
        while queue or any(r is not None for r in self.active):
            # Refill free slots.
            for s in range(self.slots):
                if self.active[s] is None and queue:
                    req = queue.pop(0)
                    first = self._admit(req, s)
                    req.generated.append(first)
                    next_tokens[s] = first
            if not any(r is not None for r in self.active):
                break
            # One fused decode step for all slots.
            toks = jnp.asarray(next_tokens[:, None])
            logits, self.cache = self._decode(self.params, toks, self.cache)
            steps += 1
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                req.generated.append(int(nxt[s]))
                next_tokens[s] = int(nxt[s])
                if req.keep_logits:
                    req.logits.append(np.asarray(logits[s]))
                if len(req.generated) >= req.max_new:
                    req.done = True
                    self.active[s] = None
        dt = time.time() - t0
        total_tokens = sum(len(r.generated) for r in requests)
        return {"requests": len(requests), "decode_steps": steps,
                "total_new_tokens": total_tokens,
                "tokens_per_s": total_tokens / max(dt, 1e-9),
                "wall_s": dt}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compat.init_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    rng = np.random.default_rng(args.seed)
    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=rng.integers(4, 32)).astype(np.int32),
                    args.max_new)
            for i in range(args.requests)]
    server = BatchedServer(cfg, params, args.slots, args.max_len)
    stats = server.run(reqs)
    print(json.dumps(stats))
    return stats


if __name__ == "__main__":
    main()
