"""One expert layer's routed sum on the chip: masked against grouped.

Where ``models/moe.GROUPED_MIN_TOKENS`` (the token count from which
``HeldExpertsMLP`` lays a call's held assignments out by expert) comes
from. Times
``moe.routed_masked`` and ``moe.routed_grouped``, the functions the layer
calls, at the published widths of the three configurations that run it, 64
experts held, bf16, over 32 to 4,096 tokens, with a seeded skewed routing
(popularity lognormal, sigma 0.7: the fullest expert gets about four times
the mean at 2,048 tokens, as ``moe_expert_load_max_over_mean`` reads in the
cells). The weights are held as the layer holds them: drawn at the
published width and zero-padded to ``grouped_experts.held_width`` of it
(nemotron's 1,856 at 1,920), both paths at that width. ``--held-width N``
holds them another width (2048: whole chunks; 1856: as published, masked
alone, what a decode step read before PR 50). A case is chained ``--chain``
times inside one jit so that dispatch does not show; a geometry runs in a
process of its own under a time limit (a shape that never returns costs
that, not the call).

    chiprun -- python3 benchmarks_dev/moe_grouped_sweep.py \\
        --out chiprun_out/moe_grouped_sweep.jsonl

Prints one JSON line a case (ms a call, the median of ``--reps``) and a
table at the end; exit 0 when every case of every geometry ran.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

# h, f, gated, experts routed over, top-k (64 of them held)
GEOMETRIES = {
    "xing4_29b": (3584, 1024, True, 64, 4),
    "nemotron3_nano_30b": (2688, 1856, False, 128, 6),
    "kanana2_30b": (2048, 768, True, 128, 6),
}
HELD = 64
TOKENS = (32, 128, 256, 512, 1024, 2048, 4096)


def routing(tokens, experts, k, seed):
    """(local (T, k) with HELD for an assignment held elsewhere, weights)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    logp = 0.7 * rng.randn(experts)
    chosen = np.argsort(-(logp + rng.gumbel(size=(tokens, experts))),
                        axis=1)[:, :k]
    w = rng.uniform(0.5, 1.5, size=(tokens, k)).astype(np.float32)
    return np.where(chosen < HELD, chosen, HELD).astype(np.int32), w


def child(args):
    import faulthandler
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from dlti_tpu.models import moe
    from dlti_tpu.ops.pallas import grouped_experts as kernel

    h, f, gated, experts, k = GEOMETRIES[args.geometry]
    held_f = args.held_width or kernel.held_width(f)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)

    def weight(key, shape, axis):
        w = (jax.random.normal(key, shape, jnp.float32)
             * shape[1] ** -0.5).astype(jnp.bfloat16)
        pad = [(0, 0)] * 3
        pad[axis] = (0, held_f - f)
        return jnp.pad(w, pad)

    w_gate = weight(keys[0], (HELD, h, f), 2) if gated else None
    w_up = weight(keys[1], (HELD, h, f), 2)
    w_down = weight(keys[2], (HELD, f, h), 1)
    device = jax.devices()[0]

    def timed(fn, xs):
        def chained(xs, *rest):
            def body(_, xs):
                return xs + fn(xs, *rest) * jnp.bfloat16(1e-3)
            return jax.lax.fori_loop(0, args.chain, body, xs)

        run = jax.jit(chained)
        faulthandler.dump_traceback_later(args.case_seconds, exit=True)
        jax.block_until_ready(run(*xs))
        faulthandler.cancel_dump_traceback_later()
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(run(*xs))
            times.append((time.perf_counter() - t) / args.chain * 1e3)
        return float(np.median(times))

    for tokens in args.tokens:
        local, w = routing(tokens, experts, k, args.seed + tokens)
        sizes = np.bincount(local.reshape(-1), minlength=HELD + 1)[:HELD]
        xs = jax.random.normal(keys[3], (tokens, h)).astype(jnp.bfloat16)
        base = {"geometry": args.geometry, "tokens": tokens,
                "held_width": held_f,
                "device": device.device_kind,
                "held_assignments": int(sizes.sum()),
                "load_max_over_mean": round(float(
                    sizes.max() / max(sizes.mean(), 1e-9)), 2)}
        operands = (xs, jnp.asarray(local), jnp.asarray(sizes, jnp.int32),
                    jnp.asarray(w), w_gate, w_up, w_down)
        tile = moe.GROUPED_TILE_ROWS
        for path in ("masked", "grouped")[
                :1 + moe.takes_grouped(moe.GROUPED_MIN_TOKENS, held_f)]:
            if path == "masked":
                def fn(xs, local, sizes, w, *weights):
                    return moe.routed_masked(xs, local, w, *weights)
            else:
                def fn(xs, local, sizes, w, *weights):
                    return moe.routed_grouped(xs, local, sizes, w,
                                              *weights)[0]
            try:
                ms = timed(fn, operands)
            except Exception as e:  # a shape the compiler refuses is a result
                ms, base = None, {**base, "error": str(e)[:200]}
            line = {**base, "path": path, "ms": ms}
            if path == "grouped" and ms is not None:
                tiles = int(np.sum(-(-sizes // tile)))
                line["tile_rows_run"] = tiles * tile
            print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometry", choices=sorted(GEOMETRIES), default=None,
                    help="run this one in this process (a child's call)")
    ap.add_argument("--tokens", type=int, nargs="+", default=list(TOKENS))
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--case-seconds", type=int, default=120,
                    help="a case whose first call takes longer ends the child")
    ap.add_argument("--geometry-seconds", type=int, default=900)
    ap.add_argument("--geometries", nargs="+", default=sorted(GEOMETRIES),
                    choices=sorted(GEOMETRIES))
    ap.add_argument("--held-width", type=int, default=0,
                    help="hold the experts this wide, whatever the rule says")
    ap.add_argument("--out", default=None, help="the lines, as a file too")
    args = ap.parse_args()
    if args.geometry:
        return child(args)

    lines, failed = [], []
    for name in args.geometries:
        cmd = [sys.executable, os.path.abspath(__file__), "--geometry", name,
               "--held-width", str(args.held_width),
               "--chain", str(args.chain), "--reps", str(args.reps),
               "--seed", str(args.seed), "--case-seconds",
               str(args.case_seconds), "--tokens", *map(str, args.tokens)]
        try:
            done = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.geometry_seconds)
            out, rc = done.stdout, done.returncode
            err = done.stderr
        except subprocess.TimeoutExpired as e:
            out = (e.stdout or b"").decode() if isinstance(
                e.stdout, bytes) else (e.stdout or "")
            rc, err = 124, "timed out"
        got = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
        lines += got
        if rc != 0:
            failed.append(name)
            print(f"{name}: exit {rc}: {err[-2000:]}", file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.writelines(json.dumps(x) + "\n" for x in lines)
    for x in lines:
        print(json.dumps(x))
    print("geometry tokens masked grouped")
    for name in args.geometries:
        for tokens in args.tokens:
            row = {x["path"]: x["ms"] for x in lines
                   if (x["geometry"], x["tokens"]) == (name, tokens)}
            if row:
                def show(v):
                    return "-" if v is None else f"{v:.3f}"
                print(name, tokens, show(row.get("masked")),
                      show(row.get("grouped")))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
