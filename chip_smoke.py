#!/usr/bin/env python3
"""Bring-up check: the sort engine, the MoE router and qwen3-1.7b serving on
one TPU, through the public entry points, at real sizes.

    python chip_smoke.py              # one TPU chip, every single-chip phase
    python chip_smoke.py --chips 4    # only the multi-chip phase (4 chips)
    python chip_smoke.py --rehearse   # tiny sizes, Pallas in interpret mode,
                                      # on whatever backend JAX finds

Every engine phase calls ``repro.engine`` with no plan or variant, so the
planner resolves the backend's own plan, and checks the result bit-for-bit
against a ``jax.numpy`` oracle (``moe_route`` against its ``xla``
variant). A phase also fails when the plan it resolved is not the TPU
heuristic's, or when the guard ladder demoted or quarantined a variant. The
serving phase runs qwen3-1.7b at its published width with weights drawn
from ``--seed`` through ``repro.serve.Scheduler``.

Each phase prints one line (name, variant, shape, PASS/FAIL, wall seconds —
the seconds are for information, not a measurement). The last line of a
run in which every phase passed is one JSON object, ``{"ok": true,
"device": {...}}``; any failure exits non-zero without it. Without a TPU
(and without ``--rehearse``) the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: bf16 tolerance of the serving checks: the scan prefill, the cached
#: decode and the parallel forward accumulate in different orders, so a
#: generated token must have a forward logit within this fraction of the
#: forward's top logit (16 bf16 ulps: bf16 keeps 8 significant bits) — the
#: forward's argmax wherever its top two logits differ by more than that

PREFILL_MARGIN = 2.0 ** -4

#: Real sizes, cut where one chip's 1200 s budget forces it (first chip run,
#: cold compile cache: every merge-tree pass runs at a few million keys per
#: second on a v5e, see PERF.md): ``seg`` from 2^22 to 2^20 keys (4096
#: ragged segments pad to the longest, 16x the keys at 2^22) and
#: ``external`` from 2^27 to 2^25 keys (still 128 MiB: three streamed
#: passes, each in two calls of 4096 grid steps).
FULL = dict(sort=1 << 24, merge=1 << 23, topk=(64, 151936, 64),
            seg=(1 << 20, 4096), runs=(64, 1 << 24), external=1 << 25,
            external_tile=0, moe=((16384, 8, 2), (16384, 64, 6)),
            serve=dict(arch="qwen3_1p7b", reduced=False, requests=8,
                       prompt=64, gen=16, slots=4),
            sharded=1 << 24)
TINY = dict(sort=1 << 12, merge=1 << 11, topk=(4, 1000, 8),
            seg=(1 << 12, 16), runs=(8, 1 << 12), external=1 << 14,
            external_tile=1 << 10, moe=((128, 8, 2), (128, 64, 6)),
            serve=dict(arch="qwen3_1p7b", reduced=True, requests=8,
                       prompt=8, gen=4, slots=4),
            sharded=1 << 12)


class PhaseFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not bool(cond):
        raise PhaseFailed(what)


class Smoke:
    """Runs phases, one line each; remembers whether all passed."""

    def __init__(self, rehearse: bool):
        from concurrent.futures import ThreadPoolExecutor
        from repro import obs
        self.obs = obs
        self.rehearse = rehearse
        self.ok = True
        self.events = []
        obs.enable()
        obs.on("*", self.events.append)
        # the jax.numpy oracles compile and run on one worker thread, in
        # order, while the engine phases compile on this one
        self.oracles = ThreadPoolExecutor(max_workers=1)

    def expect(self, op: str, *args) -> str:
        """The TPU heuristic's variant for this call. Rehearsing on another
        backend, the same plan is installed in the plan cache first, so the
        engine resolves (and runs) the TPU plan there too."""
        from repro.engine.api import infer_key
        from repro.engine.planner import default_planner, heuristic_plan
        key = infer_key(op, *args)
        tkey = key[:1] + ("tpu",) + key[2:]
        plan = heuristic_plan(op, tkey)
        if self.rehearse and key != tkey:
            default_planner.put(key, plan)
        return plan.variant

    def phase(self, name: str, op: str, variant: str, shape, fn) -> None:
        import jax
        print(f"[chip_smoke] {name} ...", file=sys.stderr, flush=True)
        start = len(self.events)
        t0 = time.perf_counter()
        try:
            jax.block_until_ready(fn())
            ev = self.events[start:]
            bad = [e for e in ev
                   if e["kind"] in ("guard.fallback", "guard.quarantine")]
            check(not bad, f"guard ladder fired: {bad[:2]}")
            if op:
                got = {e["data"]["variant"] for e in ev
                       if e["kind"] == "plan.resolve"
                       and e["data"]["op"] == op}
                check(got == {variant},
                      f"{op} resolved {sorted(got)}, expected {variant}")
            status, why = "PASS", ""
        except Exception as e:             # a phase failure, reported below
            status, why = "FAIL", f"  {type(e).__name__}: {e}"[:2000]
            self.ok = False
        dt = time.perf_counter() - t0
        print(f"[phase] {name:28s} {variant:18s} {str(shape):24s} {status} "
              f"{dt:9.3f}s{why}", flush=True)
        self.obs.reset()
        del self.events[:]


def same(a, b, what: str) -> None:
    """Bit-for-bit equality, compared on the device(s) ``a`` lives on."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    check(a.shape == b.shape, f"{what}: shape {a.shape} != {b.shape}")
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.sharding != b.sharding:
        b = jax.device_put(b, a.sharding)
    n_bad = int(jnp.count_nonzero(a != b))
    if n_bad:
        first = np.flatnonzero(np.asarray(a).reshape(-1) !=
                               np.asarray(b).reshape(-1))[:3]
        raise PhaseFailed(f"{what}: {n_bad} of {a.size} elements differ, "
                          f"first at {first.tolist()}")


# --------------------------------------------------------------------------
# single-chip phases
# --------------------------------------------------------------------------

def ints(key, n: int, bits: int, signed: bool = False):
    """``n`` uniform int32 keys of ``bits`` random bits, centred on zero if
    ``signed`` (straight from ``jax.random.bits``: ``randint`` at 2^24 keys
    takes half a minute to compile for the chip)."""
    import jax
    import jax.numpy as jnp
    u = (jax.random.bits(key, (n,), jnp.uint32) >> (32 - bits)).astype(
        jnp.int32)
    return u - (1 << (bits - 1)) if signed else u


def ragged_offsets(key, n: int, segs: int):
    """``segs + 1`` offsets of ragged segments covering ``n`` keys."""
    import jax.numpy as jnp
    cuts = jnp.sort(ints(key, segs - 1, 31) % (n + 1))
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), cuts,
                            jnp.full((1,), n, jnp.int32)])


def segment_of(offsets, n: int):
    import jax.numpy as jnp
    return jnp.searchsorted(offsets, jnp.arange(n), side="right") - 1


def falling_runs(key, offsets, n: int):
    """Keys that fall within every run of ``offsets``: 2^30 less a running
    sum of random gaps in [0, 64), restarted at each run (a zero gap is a
    tie) — sorted input made without a sort."""
    g = ints(key, n, 6)
    c = (g.cumsum() - g)[offsets[segment_of(offsets, n)]]
    return (1 << 30) - (g.cumsum() - c)


def segment_oracle(keys, offsets):
    """Per-segment stable descending order: (sorted keys, local perm)."""
    import jax.numpy as jnp
    seg = segment_of(offsets, keys.shape[0])
    by_key = jnp.argsort(keys, descending=True, stable=True)
    perm = by_key[jnp.argsort(seg[by_key], stable=True)]
    return keys[perm], perm - offsets[seg]


def outputs(x) -> tuple:
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def engine_phases(smoke: Smoke, sz: dict, seed: int) -> None:
    """Every input and its oracle's job are made first, so the oracles run
    on their thread while the phases run here, in the same order."""
    import jax
    import jax.numpy as jnp
    from repro import engine
    from repro.models.moe import expert_capacity

    key = jax.random.PRNGKey(seed)
    rk = lambda i: jax.random.fold_in(key, i)
    desc = lambda x: jnp.sort(x, descending=True)
    stable = lambda x: jnp.argsort(x, descending=True, stable=True)
    phases = []

    def add(name, op, args, shape, run, oracle, names):
        """``run()`` → the engine's output(s); ``oracle()`` → the same
        from ``jax.numpy``, computed on the oracle thread."""
        phases.append((name, op, smoke.expect(op, *args), shape, run,
                       smoke.oracles.submit(oracle), names))

    n = sz["sort"]
    xi = ints(rk(0), n, 31, signed=True)
    xf = jax.random.normal(rk(1), (n,), jnp.float32)
    for x in (xi, xf):
        add(f"sort {x.dtype.name}", "sort", (x,), (n,),
            lambda x=x: engine.sort(x), lambda x=x: desc(x), ("keys",))

    xt = ints(rk(2), n, 10)             # many ties, so stability is visible
    add("argsort stable", "argsort", (xt,), (n,), lambda: engine.argsort(xt),
        lambda: stable(xt), ("perm",))

    m = sz["merge"]
    one_run = jnp.array([0, m], jnp.int32)
    a, b = (falling_runs(rk(i), one_run, m) for i in (3, 4))
    add("merge", "merge", (a, b), (m, m), lambda: engine.merge(a, b),
        lambda: desc(jnp.concatenate([a, b])), ("keys",))

    rows, vocab, width = sz["topk"]
    logits = jax.random.normal(rk(5), (rows, vocab), jnp.float32)
    add("topk serve-vocab", "topk", (logits,), (rows, vocab, width),
        lambda: engine.topk(logits, width),
        lambda: jax.lax.top_k(logits, width), ("values", "indices"))

    ns, segs = sz["seg"]
    sk = ints(rk(6), ns, 12)
    so = ragged_offsets(rk(7), ns, segs)
    seg_want = smoke.oracles.submit(segment_oracle, sk, so)
    for op, i, what in (("segment_sort", 0, "keys"),
                        ("segment_argsort", 1, "perm")):
        add(op, op, (sk, so), (ns, segs),
            lambda op=op: getattr(engine, op)(sk, so),
            lambda i=i: seg_want.result()[i], (what,))

    n_runs, nr = sz["runs"]
    ro = ragged_offsets(rk(8), nr, n_runs)
    rkeys = falling_runs(rk(9), ro, nr)
    add("merge_runs", "merge_runs", (rkeys, ro), (n_runs, nr),
        lambda: engine.merge_runs(rkeys, ro), lambda: desc(rkeys),
        ("keys",))

    ne, tile = sz["external"], sz["external_tile"]
    # quantised floats: heavy ties, so the stable phase sees them
    xe = jnp.round(jax.random.normal(rk(10), (ne,), jnp.float32) * 64) / 64
    pos = jnp.arange(ne, dtype=jnp.int32)
    add("external_sort keys", "external_sort", (xe,), (ne,),
        lambda: engine.external_sort(xe, tile_elems=tile), lambda: desc(xe),
        ("keys",))

    def kv_oracle():
        perm = stable(xe)
        return xe[perm], perm
    add("external_sort stable kv", "external_sort", (xe,), (ne,),
        lambda: engine.external_sort(xe, values=pos, tile_elems=tile),
        kv_oracle, ("keys", "values"))

    for T, E, k in sz["moe"]:
        rl = jax.random.normal(rk(11 + E), (T, E), jnp.float32)
        cap = expert_capacity(1.25, T, k, E)
        # the oracle is the engine's own xla variant: run it here, in the
        # phase, so its plan events stay on this thread
        phases.append((f"moe_route E={E} k={k}", "moe_route",
                       smoke.expect("moe_route", rl[None], k, cap),
                       (T, E, k, cap),
                       lambda rl=rl, k=k, cap=cap: engine.moe_route(
                           rl, k, cap),
                       lambda rl=rl, k=k, cap=cap: engine.moe_route(
                           rl, k, cap, variant="xla"),
                       engine.RouteResult._fields))

    for name, op, v, shape, run, want, names in phases:
        def compare(run=run, want=want, names=names):
            got = outputs(run())
            ref = outputs(want() if callable(want) else want.result())
            check(len(got) == len(names) == len(ref),
                  f"{len(got)} outputs, {len(ref)} oracle outputs")
            for what, g, r in zip(names, got, ref):
                same(g, r, what)
        smoke.phase(name, op, v, shape, compare)


def serve_phase(smoke: Smoke, sz: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.models.transformer import lm_logits
    from repro.serve import Request, SamplingParams, Scheduler

    s = sz["serve"]
    cfg = get_config(s["arch"])
    if s["reduced"]:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    R, P, G = s["requests"], s["prompt"], s["gen"]
    prompts = np.asarray(ints(jax.random.fold_in(key, 1), R * P, 31)
                         % cfg.vocab_size).reshape(R, P)
    shape = (cfg.name, cfg.n_layers, cfg.d_model, cfg.vocab_size)
    tokens = {}

    def run(variant):
        sched = Scheduler(model, params, n_slots=s["slots"],
                          max_seq=P + G, prefill_len=P, top_k_width=64,
                          variant=variant, seed=seed)
        reqs = [Request(prompt=[int(t) for t in row],
                        max_new_tokens=G,
                        params=SamplingParams(temperature=0.0))
                for row in prompts]
        done = {c.uid: c for c in sched.run(reqs)}
        toks = np.array([done[r.uid].tokens for r in reqs], np.int32)
        check(toks.shape == (R, G), f"tokens {toks.shape}")
        check(sched.traces == 2, f"{sched.traces} traces, expected 2")
        tokens[variant] = toks

    for variant in ("flims", "xla"):
        smoke.phase(f"serve {variant} sampler", "", variant, shape,
                    lambda variant=variant: run(variant))

    def agree():
        """One parallel forward over every prompt and its flims
        continuation gives the logits behind each greedy choice (it is
        causal: position p sees the tokens up to p only)."""
        check(len(tokens) == 2, "a serving run failed")
        fl, xl = tokens["flims"], tokens["xla"]
        h = model.forward(params, {"tokens": jnp.asarray(
            np.concatenate([prompts, fl], axis=1))})
        ref = np.asarray(lm_logits(params, h[:, P - 1:P + G - 1, :], cfg),
                         np.float32)                       # (R, G, vocab)
        top = ref.max(axis=-1)
        tol = PREFILL_MARGIN * np.abs(top)
        rows = np.arange(R)[:, None]
        short = top - ref[rows, np.arange(G)[None, :], fl]
        # where the forward's top two differ by more than ``tol`` only its
        # argmax lies within ``tol`` of the top, so this is the argmax check
        # there, and accepts either side of a near-tie elsewhere
        n_arg = int((fl == ref.argmax(axis=-1)).sum())
        print(f"[serve] {n_arg} of {fl.size} flims tokens are the forward's "
              f"argmax; first tokens' shortfall below its top logit "
              f"{np.round(short[:, 0], 4).tolist()} (tolerance "
              f"{np.round(tol[:, 0], 4).tolist()}); largest shortfall "
              f"{short.max():.4f}", flush=True)
        check((short[:, 0] <= tol[:, 0]).all(),
              "a first token is not within the bf16 tolerance of the "
              "forward's top logit over its prompt")
        check((short <= tol).all(),
              "a decoded token is not within the bf16 tolerance of the "
              "forward's top logit")
        # the two samplers see the same context up to the first token where
        # they part, so there both picks must be within ``tol`` of the top:
        # tokens are identical except after a bf16 near-tie (the two step
        # programs differ, so their logits may round differently)
        for r in range(R):
            if (fl[r] == xl[r]).all():
                continue
            t = int(np.argmax(fl[r] != xl[r]))
            gap = top[r, t] - ref[r, t, xl[r, t]]
            print(f"[serve] request {r}: flims and xla part at token {t} "
                  f"({fl[r, t]} vs {xl[r, t]}, shortfalls "
                  f"{short[r, t]:.4f} and {gap:.4f}, tolerance "
                  f"{tol[r, t]:.4f})", flush=True)
            check(gap <= tol[r, t], f"request {r}: flims and xla part at "
                  f"token {t} where the forward has no near-tie")
    smoke.phase("serve tokens vs forward", "", "-", shape, agree)


# --------------------------------------------------------------------------
# multi-chip phase
# --------------------------------------------------------------------------

def multichip_phases(smoke: Smoke, sz: dict, seed: int, chips: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import engine
    from repro.models.moe import expert_capacity
    from repro.parallel.sharding import collect_sorted

    devs = jax.devices()[:chips]
    check(len(devs) == chips, f"{len(jax.devices())} devices, need {chips}")
    mesh = jax.make_mesh((chips,), ("data",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    sharded = NamedSharding(mesh, P("data"))
    one = jax.sharding.SingleDeviceSharding(devs[0])
    key = jax.random.PRNGKey(seed)
    n = sz["sharded"] * chips

    def on_devices(arr, what):
        ids = {s.device.id for s in arr.addressable_shards}
        check(len(ids) == chips, f"{what} sits on devices {sorted(ids)}")

    def zipf(k):                   # P(x >= m) = 1/m: zipf(2.0)-like tail
        u = jax.random.uniform(k, (n,), jnp.float32, 1e-9, 1.0)
        return jnp.minimum(jnp.floor(1.0 / u), 2 ** 30).astype(jnp.int32)

    keys = {"uniform": ints(key, n, 31, signed=True),
            "zipf2": zipf(jax.random.fold_in(key, 1))}
    xt = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    # the single-device oracles run on the oracle thread meanwhile
    wants = {name: smoke.oracles.submit(
        lambda x=x: jnp.sort(jax.device_put(x, one), descending=True))
        for name, x in keys.items()}
    want_topk = smoke.oracles.submit(
        lambda: jax.lax.top_k(jax.device_put(xt, one), 64))
    for name, x in keys.items():
        xs = jax.device_put(x, sharded)
        v = smoke.expect("sharded_sort", xs, mesh, "data")

        def sort_check(xs=xs, want=wants[name]):
            on_devices(xs, "input")
            res = engine.sharded_sort(xs, mesh, "data")
            on_devices(res.values, "output")
            check(not bool(np.asarray(res.overflow).any()), "overflow")
            same(collect_sorted(res), want.result(), "keys")
        smoke.phase(f"sharded_sort {name}", "sharded_sort", v,
                    (chips, sz["sharded"]), sort_check)

    xs = jax.device_put(xt, sharded)
    v = smoke.expect("sharded_topk", xs, 64, mesh, "data")

    def topk_check():
        on_devices(xs, "input")
        got_v, got_i = engine.sharded_topk(xs, 64, mesh, "data")
        want_v, want_i = want_topk.result()
        same(got_v, want_v, "values")
        same(got_i, want_i, "indices")
    smoke.phase("sharded_topk", "sharded_topk", v, (chips, sz["sharded"]),
                topk_check)

    T, E, k = sz["moe"][0]
    logits = jax.random.normal(jax.random.fold_in(key, 3), (T, E))
    cap = expert_capacity(1.25, T, k, E)
    ls = jax.device_put(logits, sharded)
    v = smoke.expect("moe_route_ep", ls, k, cap, mesh, "data")

    def ep_check():
        on_devices(ls, "input")
        shard = engine.moe_route_ep(ls, k, cap, mesh, "data")
        on_devices(shard.experts, "output")
        glob = engine.moe_route(jax.device_put(logits, one), k, cap,
                                variant="xla")
        ge, gp, gw, gk, gs = (np.asarray(x) for x in (
            glob.experts, glob.perm, glob.weights, glob.keep, glob.slabs))
        E_loc = E // chips
        A = shard.experts.shape[0] // chips
        for d in range(chips):
            lane = slice(d * A, (d + 1) * A)
            cnt = int(np.asarray(shard.count)[d])
            kept = np.asarray(shard.keep[lane][:cnt])
            perm = np.asarray(shard.perm[lane][:cnt])[kept]
            mine = ((ge // E_loc) == d) & gk
            o, g = np.argsort(perm), np.argsort(gp[mine])
            same(perm[o], gp[mine][g], f"device {d} kept pairs")
            same(np.asarray(shard.weights[lane][:cnt])[kept][o],
                 gw[mine][g], f"device {d} weights")
            same(np.asarray(shard.slabs[lane][:cnt])[kept][o],
                 gs[mine][g] - d * E_loc * cap, f"device {d} slabs")
    smoke.phase(f"moe_route_ep E={E} k={k}", "moe_route_ep", v,
                (chips, T, E, k, cap), ep_check)


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phase on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes with Pallas in interpret mode, on any "
                         "backend (the CPU rehearsal of this script)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repro package is not next to this script "
              "(run it from a checkout of the repository)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    if args.rehearse and args.chips > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={args.chips}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke.py: JAX found no TPU (platform {platform!r}); "
              "this check runs on the chip, or with --rehearse",
              file=sys.stderr)
        return 1
    sz = TINY if args.rehearse else FULL
    smoke = Smoke(args.rehearse)
    print(f"[chip_smoke] {platform} {devices[0].device_kind} x{len(devices)}"
          f" jax {jax.__version__}{' (rehearsal)' if args.rehearse else ''}",
          flush=True)
    if args.chips > 1:
        multichip_phases(smoke, sz, args.seed, args.chips)
    else:
        engine_phases(smoke, sz, args.seed)
        serve_phase(smoke, sz, args.seed)
    smoke.oracles.shutdown(cancel_futures=True)
    if not smoke.ok:
        print("[chip_smoke] FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
