"""The latent decode kernel alone on the chip: what its loop waits for.

Times ``ops.pallas.latent_attention.latent_decode_attention`` (the line
``production``: whatever the checkout it runs in holds) and a lab copy of
its kernel with the loop's parts taken apart, at the shapes of the two cells
that run it: 32 rows, 32 heads, rows of 576 values laid in 640 of which 512
are the values, blocks of 16, bf16; ``doc_turns`` 16,384 blocks and 544 a
row, contexts a document (lognormal, median 6,144, 4,096-8,192) plus a part
of an answer; ``fresh_docs`` 8,192 blocks and 512 a row, contexts a prompt
(median 2,048, 512-4,096) plus a part of an answer: the traffic files'
draws. A row's live blocks lie scattered over the pool.

The lab kernel's knobs (none of them is the program's), a case being
``key=value,...`` over the kernel as it was before PR 55 (``d=2,k=256`` and
every other knob at its first value):

* ``mode``: ``full``; ``copies`` (every tile copied and waited for, no
  body); ``body`` (one tile copied before the loop, every step computes on
  it).
* ``d``: slots of the ring; a step starts tile ``i + d - 1``.
* ``k``: keys a tile.
* ``wait``: ``each`` (one wait a block copy) or ``slot`` (one wait for the
  slot's whole byte count; every ``guard=clamp`` case waits so).
* ``guard``: ``when`` (a tile past the schedule's end is not copied: a
  branch round the copies) or ``clamp`` (it copies the last tile again: no
  branch; the copies left over are waited for after the loop).
* ``issue``: the step's copies start ``first`` or ``last`` in it.
* ``flat``: 0 the state of a row's first tile is set under a branch; 1 it is
  chosen by a select; 2 it is reset where a row's last tile writes out.
* ``fin``: 1 writes a row's output at every tile, not under a branch at its
  last.
* ``res``: 1 the wrapper replaces the table's blocks past a row's context by
  the row's last live block; 0 the kernel does, a copy at a time (row,
  min(logical, last live)).
* ``pipe``: 1 a step scores tile ``i + 1`` beside tile ``i``'s softmax and
  values (needs ``guard=clamp`` and ``d`` of 3 or more).

A call is chained ``--chain`` times inside one jit (the output feeds the
next call's queries) so that dispatch does not show; the lab's schedule is
made once outside the chain, ``production`` makes its own inside it as a
decode step does. Every ``full`` lab line is checked against
``production``'s result first. A geometry runs in a process of its own
under a time limit: a wait that never returns costs that, not the call.

    chiprun -- python3 benchmarks_dev/latent_kernel_sweep.py \\
        --commit $(git rev-parse --short HEAD) \\
        --out chiprun_out/latent_kernel_sweep.jsonl

Prints one JSON line a case (us a call, the median of ``--reps``; us a 256
live keys of tiles; the share of the roofline
``benchmark/lib/latent_bytes.kernel_work`` counts: 1,152 B a live row at
819 GB/s) and a table at the end; exit 0 when every case of every geometry
ran.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROWS, HEADS, LATENT, VALUES, BLOCK = 32, 32, 576, 512, 16
WIDTH = -(-LATENT // 128) * 128
# blocks in the pool, blocks a row, the context's draw, the answer's draw
GEOMETRIES = {
    "doc_turns": (16384, 544, (6144, 0.25, 4096, 8192), (96, 0.5, 32, 256)),
    "fresh_docs": (8192, 512, (2048, 0.5, 512, 4096), (128, 0.5, 32, 384)),
}
HBM_BYTES_PER_S = 819e9  # benchmark/lib/peaks.json, "TPU v5 lite"

KNOBS = {"mode": "full", "d": 2, "k": 256, "wait": "each", "guard": "when",
         "issue": "first", "flat": 0, "fin": 0, "res": 0, "pipe": 0}
# What PR 55's kernel is, as the lab builds it.
FINAL = "guard=clamp,flat=2,res=1,pipe=1"
# The kernel as it was, its copies alone, its body alone; then the two-slot
# form over depth x tile; the knobs one at a time at three slots and
# together; the final form over depth x tile, its copies and its body.
CASES = ["d=2", "d=2,mode=copies", "d=2,mode=body", "d=2,wait=slot",
         "d=2,k=512,mode=body"]
CASES += [f"d={d},k={k}{mode}" for k in (256, 512) for d in (2, 3, 4, 6)
          for mode in ("", ",mode=copies") if (d, k) != (2, 256)]
CASES += ["d=3," + knobs for knobs in (
    "guard=clamp", "issue=last", "guard=clamp,issue=last", "flat=1", "res=1",
    "guard=clamp,issue=last,flat=1,res=1", "guard=clamp,pipe=1",
    "guard=clamp,pipe=1,flat=1", "guard=clamp,pipe=1,flat=1,res=1",
    "guard=clamp,flat=2,res=1")]
CASES += [f"d={d},k={k},{FINAL}" for k, depths in (
    (256, (3, 4, 5, 6, 8)), (384, (3, 4, 6)), (512, (3, 4, 6)),
    (1024, (3, 4))) for d in depths]
CASES += [f"d=4,k=384,{FINAL},mode=copies", f"d=4,k=384,{FINAL},mode=body",
          f"d=4,k=256,{FINAL},fin=1", f"d=4,k=256,{FINAL},issue=last"]


def parse_case(text):
    case = dict(KNOBS)
    for item in text.split(","):
        key, value = item.split("=")
        case[key] = type(KNOBS[key])(value)
    if case["pipe"] and (case["guard"] != "clamp" or case["d"] < 3):
        raise ValueError(f"{text}: pipe=1 needs guard=clamp and d >= 3")
    return case


def contexts(name, seed):
    """A decode round's 32 contexts, as the cell's traffic draws them."""
    sys.path.insert(0, ROOT)
    from benchmark.lib.traffic import lognormal_int

    _, max_blocks, prompt, answer = GEOMETRIES[name]
    rng = random.Random(seed)
    return [min(lognormal_int(rng, *prompt)
                + rng.randrange(lognormal_int(rng, *answer)),
                max_blocks * BLOCK) for _ in range(ROWS)]


def lab_call(max_blocks, dtype, case, *, scale, interpret):
    """The lab kernel as a function of (lens, table, row, tile, total, q,
    pool), ``q`` padded to the pool's width; ``table`` the block table, with
    ``res=1`` as ``resolve`` lays it out."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from dlti_tpu.ops.pallas.paged_attention import NEG_INF

    mode, depth, keys = case["mode"], case["d"], case["k"]
    clamp, flat, pipe = case["guard"] == "clamp", case["flat"], case["pipe"]
    T = keys // BLOCK

    def kernel(lens_ref, table_ref, row_ref, tile_ref, total_ref, q_ref,
               pool_hbm, o_ref, buf, sem, m_scratch, l_scratch, acc_scratch):
        total = total_ref[0]
        o_ref[...] = jnp.zeros_like(o_ref)

        def entry(k):
            return jnp.minimum(k, total - 1) if clamp else k

        def slot_of(k):
            return 0 if mode == "body" else jax.lax.rem(k, depth)

        def copies(k):
            i, slot = entry(k), slot_of(k)
            row, j = row_ref[i], tile_ref[i]
            if case["res"]:
                first = row * (table_ref.shape[0] // ROWS) + j * T
                ids = [table_ref[first + t] for t in range(T)]
            else:
                last = jnp.minimum((lens_ref[row] - 1) // BLOCK,
                                   max_blocks - 1)
                ids = [table_ref[row, jnp.minimum(j * T + t, last)]
                       for t in range(T)]
            return [pltpu.make_async_copy(
                pool_hbm.at[phys], buf.at[slot, pl.ds(t * BLOCK, BLOCK)],
                sem.at[slot]) for t, phys in enumerate(ids)]

        def start(k, always=False):
            def go():
                for copy in copies(k):
                    copy.start()
            if mode == "body" and not always:
                return
            if clamp:
                go()
            else:
                pl.when(k < total)(go)

        def wait(k, always=False):
            if mode == "body" and not always:
                return
            if clamp or case["wait"] == "slot":
                slot = slot_of(k)
                pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                      sem.at[slot]).wait()
            else:
                for copy in copies(k):
                    copy.wait()

        k_in_tile = jax.lax.broadcasted_iota(jnp.int32, (HEADS, keys), 1)

        def scores(k):
            i, slot = entry(k), slot_of(k)
            row, j = row_ref[i], tile_ref[i]
            rows = buf[slot]
            s = jax.lax.dot_general(
                q_ref[row].astype(rows.dtype), rows,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            return jnp.where(j * keys + k_in_tile < lens_ref[row], s, NEG_INF)

        def reset():
            m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
            l_scratch[...] = jnp.zeros_like(l_scratch)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

        def update(i, s):
            row, j = row_ref[i], tile_ref[i]
            if flat == 0:
                pl.when(j == 0)(reset)
            m_prev, l_prev, acc_prev = (m_scratch[...], l_scratch[...],
                                        acc_scratch[...])
            if flat == 1:
                m_prev = jnp.where(j == 0, NEG_INF, m_prev)
                l_prev = jnp.where(j == 0, 0.0, l_prev)
                acc_prev = jnp.where(j == 0, 0.0, acc_prev)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                p.astype(buf.dtype), buf[slot_of(i), :, :VALUES],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scratch[...] = m_new
            l_scratch[...] = l_new
            acc_scratch[...] = acc_new
            return row, j == (lens_ref[row] - 1) // keys, acc_new, l_new

        def finalize(row, is_last, acc_new, l_new):
            def write():
                o_ref[row] = (acc_new / l_new).astype(o_ref.dtype)

            if case["fin"]:
                write()

            @pl.when(is_last)
            def _():
                if not case["fin"]:
                    write()
                if flat == 2:
                    reset()

        def step(i, carry):
            if case["issue"] == "first":
                start(i + depth - 1)
            wait(i + 1 if pipe else i)
            if mode != "copies":
                if pipe:
                    s, carry = carry, scores(i + 1)
                else:
                    s = scores(i)
                done = update(i, s)
            if case["issue"] == "last":
                start(i + depth - 1)
            if mode != "copies":
                finalize(*done)
            return carry

        def run():
            if flat == 2:
                reset()
            if mode == "body":
                start(0, always=True)
                wait(0, always=True)
            for k in range(depth - 1):
                start(k)
            carry = 0
            if pipe:
                wait(0)
                if mode != "copies":
                    carry = scores(0)
            jax.lax.fori_loop(0, total, step, carry)
            if clamp:  # what the last steps sent past the schedule's end
                for k in range(1 if pipe else 0, depth - 1):
                    wait(total + k)

        if clamp or mode == "body":
            pl.when(total > 0)(run)
        else:
            run()

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, *_: (0,) * len(shape))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,),
            in_specs=[whole((ROWS, HEADS, WIDTH)),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=whole((ROWS, HEADS, VALUES)),
            scratch_shapes=[
                pltpu.VMEM((depth, keys, WIDTH), dtype),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.VMEM((HEADS, 1), jnp.float32),
                pltpu.VMEM((HEADS, 1), jnp.float32),
                pltpu.VMEM((HEADS, VALUES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((ROWS, HEADS, VALUES), dtype),
        interpret=interpret,
        name="lab_latent_" + "_".join(str(v) for v in case.values()),
    )


def resolve(tables, lens, T):
    """The table as ``res=1`` reads it: flat, whole tiles a row, a block past
    a row's context replaced by the row's last live block."""
    import jax.numpy as jnp

    last = jnp.maximum(lens - 1, 0) // BLOCK
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % T)))
    return jnp.where(
        jnp.arange(tables.shape[1])[None, :] <= last[:, None], tables,
        jnp.take_along_axis(tables, last[:, None], 1)).reshape(-1)


def child(args):
    import faulthandler
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from dlti_tpu.ops.pallas.latent_attention import latent_decode_attention
    from dlti_tpu.ops.pallas.paged_attention import live_tiles

    num_blocks, max_blocks, _, _ = GEOMETRIES[args.geometry]
    if args.tiny:  # the control flow, on the CPU
        num_blocks, max_blocks = 256, 64
    scale = 192 ** -0.5
    device = jax.devices()[0]
    lens = [n % 1000 if args.tiny else n
            for n in contexts(args.geometry, args.seed)]
    rng = np.random.RandomState(args.seed % (2 ** 31))
    spread = rng.permutation(num_blocks)
    tables, at = np.zeros((ROWS, max_blocks), np.int32), 0
    for r, n in enumerate(lens):
        live = -(-n // BLOCK)
        tables[r, :live] = spread[(at + np.arange(live)) % num_blocks]
        at += live
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 2)
    pool = jax.random.normal(keys[0], (num_blocks, BLOCK, WIDTH),
                             jnp.bfloat16).at[..., LATENT:].set(0)
    q = jax.random.normal(keys[1], (ROWS, HEADS, LATENT), jnp.bfloat16)
    lens_d, tables_d = jnp.asarray(lens, jnp.int32), jnp.asarray(tables)
    live_rows = int(sum(lens))
    base = {"geometry": args.geometry, "device": device.device_kind,
            "commit": args.commit, "seed": args.seed,
            "mean_context": round(live_rows / ROWS, 1), "chain": args.chain}

    def timed(fn, q, *rest):
        """us a call of ``fn(q, *rest)``. Everything large is an argument:
        an array a jitted function closes over is compiled in as a constant
        (the pool is 335 MB)."""
        def chained(q, *rest):
            def body(_, q):
                out = fn(q, *rest)
                return q + jnp.pad(out, ((0, 0), (0, 0), (
                    0, q.shape[-1] - VALUES))) * jnp.bfloat16(1e-3)
            return jax.lax.fori_loop(0, args.chain, body, q)

        run = jax.jit(chained)
        faulthandler.dump_traceback_later(args.case_seconds, exit=True)
        jax.block_until_ready(run(q, *rest))
        faulthandler.cancel_dump_traceback_later()
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(run(q, *rest))
            times.append((time.perf_counter() - t) / args.chain * 1e6)
        return float(np.median(times))

    def emit(line):
        """A line as it is read: a child that is killed has written its own."""
        text = json.dumps({**base, **line})
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(text + "\n")

    tiles_256 = sum(-(-n // 256) for n in lens)
    floor_us = live_rows * LATENT * 2 / HBM_BYTES_PER_S * 1e6

    def report(line, us):
        emit({**line, "us": round(us, 2),
              "us_per_256_keys_of_tiles": round(us / tiles_256, 4),
              "ns_per_live_row": round(us * 1e3 / live_rows, 3),
              "roofline_pct": round(100 * floor_us / us, 2)})

    def production(q, pool, tables, lens):
        return latent_decode_attention(q, pool, tables, lens,
                                       value_dim=VALUES, scale=scale,
                                       interpret=args.tiny)

    want = np.asarray(production(q, pool, tables_d, lens_d), np.float32)
    report({"case": "production"},
           timed(production, q, pool, tables_d, lens_d))

    q_wide = jnp.pad(q, ((0, 0), (0, 0), (0, WIDTH - LATENT)))
    for text in args.cases:
        case = parse_case(text)
        T = case["k"] // BLOCK
        row, tile, total = live_tiles(lens_d, case["k"], 0,
                                      ROWS * -(-max_blocks // T))
        table = resolve(tables_d, lens_d, T) if case["res"] else tables_d
        call = lab_call(max_blocks, jnp.bfloat16, case, scale=scale,
                        interpret=args.tiny)
        schedule = (lens_d, table, row, tile, total)

        def fn(q, pool, *schedule, call=call):
            return call(*schedule, q, pool)

        line = {"case": text, **case}
        try:
            if case["mode"] == "full":
                faulthandler.dump_traceback_later(args.case_seconds,
                                                  exit=True)
                got = np.asarray(fn(q_wide, pool, *schedule), np.float32)
                faulthandler.cancel_dump_traceback_later()
                line["max_abs_diff_to_production"] = round(float(
                    np.max(np.abs(got - want))), 5)
            us = timed(fn, q_wide, pool, *schedule)
        except Exception as e:  # a shape the compiler refuses is a result
            emit({**line, "error": str(e)[:300]})
            continue
        report(line, us)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometry", choices=sorted(GEOMETRIES), default=None,
                    help="run this one in this process (a child's call)")
    ap.add_argument("--geometries", nargs="+", default=sorted(GEOMETRIES),
                    choices=sorted(GEOMETRIES))
    ap.add_argument("--cases", nargs="+", default=CASES,
                    help="key=value,... over " + ",".join(
                        f"{k}={v}" for k, v in KNOBS.items()))
    ap.add_argument("--chain", type=int, default=12)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--commit", default="unknown",
                    help="the checkout's commit, for the lines (the chip's "
                    "copy has no .git)")
    ap.add_argument("--case-seconds", type=int, default=90,
                    help="a case whose first call takes longer ends the child")
    ap.add_argument("--geometry-seconds", type=int, default=900)
    ap.add_argument("--tiny", action="store_true",
                    help="small pool, interpreted: the control flow on a CPU")
    ap.add_argument("--out", default=None, help="the lines, as a file too")
    args = ap.parse_args()
    for text in args.cases:
        parse_case(text)
    if args.geometry:
        return child(args)

    lines, failed = [], []
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        open(args.out, "w").close()
    for name in args.geometries:
        cmd = [sys.executable, os.path.abspath(__file__), "--geometry", name,
               "--chain", str(args.chain), "--reps", str(args.reps),
               "--seed", str(args.seed), "--commit", args.commit,
               "--case-seconds", str(args.case_seconds),
               "--cases", *args.cases]
        cmd += ["--tiny"] * args.tiny
        cmd += ["--out", os.path.abspath(args.out)] if args.out else []
        try:
            done = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.geometry_seconds)
            out, rc, err = done.stdout, done.returncode, done.stderr
        except subprocess.TimeoutExpired as e:
            out = (e.stdout or b"").decode() if isinstance(
                e.stdout, bytes) else (e.stdout or "")
            rc, err = 124, "timed out"
        lines += [json.loads(x) for x in out.splitlines()
                  if x.startswith("{")]
        if rc != 0:
            failed.append(name)
            print(f"{name}: exit {rc}: {err[-2000:]}", file=sys.stderr)
    print("geometry us us_per_256_keys_of_tiles roofline_pct diff case")
    for x in lines:
        print(x["geometry"], x.get("us", x.get("error")),
              x.get("us_per_256_keys_of_tiles", "-"),
              x.get("roofline_pct", "-"),
              x.get("max_abs_diff_to_production", "-"), x["case"])
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
