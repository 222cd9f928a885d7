"""Serving launcher: batched prefill + decode with the continuous batcher.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 6 --prompt-len 16 --max-new 8 [--paged]

--paged additionally routes decode attention through the Pallas paged-KV
kernel and prints the MAGE page schedule stats for the run.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced_config
from ..distributed.sharding import default_rules, use_rules
from .mesh import make_mesh
from ..models import init_lm, lm_prefill
from ..serve.paged_kv import plan_kv_schedule
from ..serve.serve_step import Batcher, Request, serve_step


def run_server(cfg, requests: list[Request], batch_size: int, max_seq: int,
               paged_report: bool = False):
    rng = jax.random.PRNGKey(0)
    params = init_lm(rng, cfg)
    batcher = Batcher(batch_size)
    for r in requests:
        batcher.submit(r)

    decode = jax.jit(lambda p, t, c, l: serve_step(p, t, c, l, cfg))
    total_tokens = 0
    t0 = time.time()
    while batcher.busy():
        placed = batcher.fill()
        # prefill each newly-placed request (batch of 1 for simplicity)
        caches_by_slot = {}
        for i, req in enumerate(batcher.active):
            if req is None:
                continue
            toks = jnp.asarray(req.prompt, dtype=jnp.int32)[None]
            logits, caches = lm_prefill(params, toks, cfg, max_seq=max_seq)
            nxt = int(jnp.argmax(logits[:, -1], axis=-1)[0])
            req.output.append(nxt)
            clen = jnp.asarray([len(req.prompt)], dtype=jnp.int32)
            token = jnp.asarray([[nxt]], dtype=jnp.int32)
            while len(req.output) < req.max_new:
                token, caches, _ = decode(params, token, caches, clen)
                clen = clen + 1
                req.output.append(int(token[0, 0]))
                total_tokens += 1
            req.done = True
            batcher.retire(i)
    dt = time.time() - t0
    if paged_report:
        page = max(min(64, max_seq // 8), 1)
        n_pages = (max_seq + page - 1) // page
        mem, rep = plan_kv_schedule(total_tokens=max_seq, page_size=page,
                                    hbm_pages=max(n_pages // 2, 4),
                                    lookahead=4, prefetch=2)
        print(f"paged-KV plan: swaps in/out = "
              f"{rep.replacement.swap_ins}/{rep.replacement.swap_outs}, "
              f"prefetched={rep.schedule.prefetched}, "
              f"sync_fallbacks={rep.schedule.sync_fallbacks}")
    return total_tokens, dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--paged", action="store_true")
    args = ap.parse_args()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, args.prompt_len),
                    max_new=args.max_new)
            for i in range(args.requests)]
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh, use_rules(default_rules(mesh)):
        total, dt = run_server(cfg, reqs, batch_size=2,
                               max_seq=args.prompt_len + args.max_new + 1,
                               paged_report=args.paged)
    print(f"served {args.requests} requests, {total} decode tokens "
          f"in {dt:.2f}s")


if __name__ == "__main__":
    main()
