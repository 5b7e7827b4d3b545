"""Thin serving CLI over ``repro.serve`` (DESIGN.md §10).

Decoder architectures serve through the continuous-batching
:class:`repro.serve.Scheduler`: one shape-static ``lax.scan`` prefill per
admission (one compile + one device call — never a per-token python loop),
a static super-batch decode step, and ONE ragged engine top-k sampling
call per step for every live request. Encoder-decoder architectures keep a
compact legacy loop here (their cross-attention prefill is already a
single ``model.prefill`` call).

The sampler routes through ``repro.engine`` — the planner picks the FLiMS
merge-tree top-k or ``lax.top_k`` per backend, ``--flims-topk``/``--lax-topk``
pin a variant, and ``--plans plans.json`` preloads an autotuned plan table.

Run small on CPU:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --batch 4 --prompt-len 16 --gen 32 --top-p 0.9 --stats 8
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.models.model import build_model, sample_topk
from repro.obs.reporting import serve_stats_line
from repro.serve import Request, SamplingParams, Scheduler


def _serve_encdec(model, cfg, params, prompts, key, gen, max_seq,
                  use_flims_topk, topk):
    """Compact legacy loop for encoder-decoder archs: batched prefill is
    already one call; decode is one jitted step."""
    batch, prompt_len = prompts.shape
    frames = jax.random.normal(jax.random.fold_in(key, 2),
                               (batch, 32, cfg.d_model))
    _, cache = model.prefill(params, {"frames": frames, "tokens": prompts},
                             max_seq)

    @jax.jit
    def step(params, tok, pos, cache, key):
        logits, cache = model.decode_step(params, tok, pos, cache)
        nxt = sample_topk(key, logits, k=topk, use_flims=use_flims_topk)
        return nxt, cache

    tok = prompts[:, -1]
    out = []
    t0 = time.time()
    for t in range(gen):
        key, sk = jax.random.split(key)
        tok, cache = step(params, tok,
                          jnp.full((batch,), prompt_len + t, jnp.int32),
                          cache, sk)
        out.append(np.asarray(tok))    # np.asarray blocks: full-step latency
    return np.stack(out, axis=1), time.time() - t0


def serve(cfg, batch: int, prompt_len: int, gen: int, max_seq: int = 0,
          use_flims_topk: bool = None, seed: int = 0, topk: int = 16,
          stats_every: int = 0, temperature: float = 1.0,
          top_p: float = 1.0, min_p: float = 0.0, n_slots: int = 0,
          deadline_s: float = 0.0, max_waiting: int = 0):
    """Serve ``batch`` random prompts to completion; returns
    ``(tokens (batch, gen), wall_seconds)``. Rows retired early (deadline
    or poison isolation) are padded with ``-1``."""
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    max_seq = max_seq or (prompt_len + gen)
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)

    if cfg.arch_kind == "encdec":
        return _serve_encdec(model, cfg, params, prompts, key, gen, max_seq,
                             use_flims_topk, topk)

    if stats_every:
        obs.enable()
    variant = (None if use_flims_topk is None
               else ("flims" if use_flims_topk else "xla"))
    sched = Scheduler(model, params, n_slots=n_slots or batch,
                      max_seq=max_seq, prefill_len=prompt_len,
                      top_k_width=topk, variant=variant,
                      max_waiting=max_waiting, seed=seed)
    sp = SamplingParams(temperature=temperature, top_p=top_p, min_p=min_p)
    reqs = [Request(prompt=[int(x) for x in row], max_new_tokens=gen,
                    params=sp, deadline_s=deadline_s or None)
            for row in np.asarray(prompts)]
    for r in reqs:
        sched.submit(r)
    t0 = time.time()
    it = 0
    while sched.waiting or sched.live:
        sched.admit()
        if sched.live:
            sched.step()
        it += 1
        if stats_every and it % stats_every == 0:
            print(serve_stats_line(obs.snapshot(), step=it), flush=True)
    dt = time.time() - t0
    by_uid = {c.uid: c for c in sched.completed}
    # deadline/poison retirements can be short — pad rows to (batch, gen)
    toks = np.full((len(reqs), gen), -1, np.int32)
    for i, r in enumerate(reqs):
        got = by_uid[r.uid].tokens
        toks[i, :len(got)] = got
    return toks, dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--slots", type=int, default=0,
                    help="static super-batch width (0 = --batch; fewer "
                         "slots than requests exercises continuous "
                         "admission)")
    ap.add_argument("--topk", type=int, default=16,
                    help="sampler candidate-prefix width")
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="sampling temperature (<= 0 -> greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling cut within the top-k prefix")
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min-p sampling cut within the top-k prefix")
    ap.add_argument("--lax-topk", action="store_true",
                    help="pin the sampler to lax.top_k")
    ap.add_argument("--flims-topk", action="store_true",
                    help="pin the sampler to the FLiMS merge-tree top-k")
    ap.add_argument("--plans", default=None,
                    help="JSON plan table to preload into the engine")
    ap.add_argument("--save-plans", default=None, metavar="OUT",
                    help="write the engine's plan table (autotuned or "
                         "resolved during this run) back to JSON, so it "
                         "round-trips into a later --plans")
    ap.add_argument("--deadline", type=float, default=0.0, metavar="S",
                    help="per-request wall-clock deadline in seconds; "
                         "requests still live past it retire with "
                         "status=TIMEOUT (0 = off)")
    ap.add_argument("--max-waiting", type=int, default=0, metavar="N",
                    help="bound the submit queue at N requests; a full "
                         "queue rejects with QueueFull backpressure "
                         "(0 = unbounded)")
    ap.add_argument("--verify", action="store_true",
                    help="enable the guard layer's in-graph postcondition "
                         "checks (sortedness/permutation monitors on every "
                         "engine call; see DESIGN.md §11)")
    ap.add_argument("--stats", type=int, default=0, metavar="N",
                    help="enable repro.obs and print a [serve] line every N "
                         "loop iterations (p50/p99 from the serve.step "
                         "timer histogram, tok/s, occupancy, trace count), "
                         "plus a final obs report")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.plans:
        from repro import engine
        engine.load_plans(args.plans)
    use_flims = None                     # planner decides per backend
    if args.lax_topk:
        use_flims = False
    elif args.flims_topk:
        use_flims = True
    if args.stats:
        obs.enable()
    if args.verify:
        from repro.guard import enable_verify
        enable_verify()
    toks, dt = serve(cfg, args.batch, args.prompt_len, args.gen,
                     use_flims_topk=use_flims, topk=args.topk,
                     stats_every=args.stats, temperature=args.temperature,
                     top_p=args.top_p, min_p=args.min_p,
                     n_slots=args.slots, deadline_s=args.deadline,
                     max_waiting=args.max_waiting)
    print(f"[serve] generated {toks.shape} tokens in {dt:.2f}s "
          f"({toks.shape[0] * toks.shape[1] / dt:.1f} tok/s)")
    print(toks[:2, :16])
    if args.stats:
        print(obs.report())
    if args.save_plans:
        from repro import engine
        engine.save_plans(args.save_plans)
        print(f"[serve] wrote engine plan table to {args.save_plans}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
