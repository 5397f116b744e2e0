"""probe_row_scatter: what a row of a chunk costs to add into carried
``(T, d)`` float32 sums, by XLA's scatter-add and by the Pallas row kernel.

The held experts' loop (``parallel/experts.local_expert_ffn``) adds a
chunk's rows into a carried sum twice a trip.  This is PR 57's probe of
that one line made a tool (``PERF.md`` section 6 has both tables): a
carried ``(tokens, d)`` float32 array, ``--calls`` scatter-adds a loop
inside one program (each of the same ``y`` under its own tokens and
weights: a slice of a stack of them would be a copy a call), the
chunk's rows sorted by group with a group's tokens ascending as
``experts.local_dispatch`` leaves them.  A call's cost is what a loop of
three times the calls takes longer, a call: a program's launch, and a
copy of its argument where the program's boundary holds it in another
layout than the kernel reads (seen on the v5e), are paid once and drop
out.  By

- ``xla``: ``acc.at[token].add(y * scale[:, None])`` over every row of
  the chunk, the rows past the live count zeroed (the loop's lines where
  ``ops/row_scatter.supported`` says no);
- ``kernel``: ``ops/row_scatter.row_scatter_add`` on the sums a row as
  tiles of its own (``as_tiles``), which walks the live rows alone;
- ``to_rows``: the sums from that form back to rows, paid once a loop
  (half of there, an add and back again, in one program).

It prints ms a call and us a live row for each shape and writes them as
JSON.  The two forms' sums are compared on the device (``max_abs_diff``):
on a chip that is the kernel's test against copies that really overlap.
Times mean something on a TPU only; elsewhere the kernel runs interpreted,
the record says so (``platform``), and small shapes are the ones to ask
for.

    python -m ompi_tpu.tools.probe_row_scatter --out chiprun_out/pr66/probe.json
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np


def chunk_tables(rng, tokens: int, rows: int, groups: int, live: int):
    """(token (rows,), offsets (groups + 1,)) of one chunk: ``live`` rows
    dealt to ``groups`` groups in uneven runs, a group's tokens distinct
    and ascending, the rows past ``live`` naming token 0 (what the loop's
    padded ``order`` gives them)."""
    cuts = np.sort(rng.integers(0, live + 1, groups - 1))
    offsets = np.concatenate([[0], cuts, [live]]).astype(np.int32)
    token = np.zeros(rows, np.int32)
    for lo, hi in zip(offsets[:-1], offsets[1:]):
        token[lo:hi] = np.sort(rng.choice(tokens, hi - lo, replace=False))
    return token, offsets


def timed_ms(fn, acc, repeats: int) -> float:
    """The median of ``repeats`` calls of ``fn(acc)``, each waited for
    and each given the last one's result (``fn`` may take its argument's
    buffer), after one that compiles."""
    import jax

    acc = jax.block_until_ready(fn(acc))
    took = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        acc = jax.block_until_ready(fn(acc))
        took.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(took)


def probe(tokens: int, rows: int, d: int, groups: int, live_share: float,
          calls: int, repeats: int, seed: int, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops import row_scatter

    rng = np.random.default_rng(seed)
    live = int(rows * live_share)
    tables = [chunk_tables(rng, tokens, rows, groups, live)
              for _ in range(3 * calls)]
    token = jnp.asarray(np.stack([t for t, _ in tables]))
    offsets = jnp.asarray(np.stack([o for _, o in tables]))
    key_y, key_s = jax.random.split(jax.random.key(seed))
    y = jax.random.normal(key_y, (rows, d), jnp.float32)
    scale = jax.random.uniform(key_s, (3 * calls, rows), jnp.float32)

    def by_xla(i, acc):
        term = jnp.where((jnp.arange(rows) < live)[:, None],
                         y * scale[i][:, None], 0.0)
        return acc.at[token[i]].add(term)

    def by_kernel(i, acc):
        return row_scatter.row_scatter_add(
            acc, token[i], offsets[i], y, scale[i], interpret=interpret)

    def loop(body, n):
        return jax.jit(lambda acc: jax.lax.fori_loop(0, n, body, acc),
                       donate_argnums=0)

    def ms_a_call(body, sums):
        """What one more call costs a loop: a loop of three times the
        calls against one of ``calls``, so that what a program pays once
        (its launch, the layout of its argument and result) drops out."""
        few, many = (timed_ms(loop(body, n), sums(), repeats)
                     for n in (calls, 3 * calls))
        return (many - few) / (2 * calls)

    def zeros():
        return jnp.zeros((tokens, d), jnp.float32)

    def tiles():
        return row_scatter.as_tiles(zeros())

    want = loop(by_xla, calls)(zeros())
    got = row_scatter.as_rows(loop(by_kernel, calls)(tiles()))
    there_and_back = jax.jit(
        lambda acc: row_scatter.as_tiles(row_scatter.as_rows(acc) + 1.0),
        donate_argnums=0)
    row = {
        "tokens": tokens, "rows": rows, "d": d, "groups": groups,
        "live_rows": live, "calls": calls,
        "supported": row_scatter.supported(rows, d),
        "max_abs_diff": float(jnp.max(jnp.abs(want - got))),
        "xla_ms_a_call": ms_a_call(by_xla, zeros),
        "kernel_ms_a_call": ms_a_call(by_kernel, tiles),
        "to_rows_ms": timed_ms(there_and_back, tiles(), repeats) / 2,
    }
    for form in ("xla", "kernel"):
        row[form + "_us_a_live_row"] = (
            row[form + "_ms_a_call"] * 1e3 / max(live, 1))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="probe_row_scatter")
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--rows", default="1024,2048,8192")
    ap.add_argument("--widths", default="1024,2048,2560")
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--live", default="1.0,0.9",
                    help="the shares of a chunk's rows that hold a slot")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block-rows", type=int, default=None,
                    help="rows a grid step of the kernel takes, in place "
                    "of ops/row_scatter.BLOCK_ROWS")
    ap.add_argument("--out", default=None, help="write the JSON here")
    args = ap.parse_args(argv)

    import jax

    from ompi_tpu.base.jaxenv import pallas_interpret

    from ompi_tpu.ops import row_scatter

    if args.block_rows:
        row_scatter.BLOCK_ROWS = args.block_rows
    device = jax.devices()[0]
    res = {"platform": device.platform, "device_kind": device.device_kind,
           "interpret": pallas_interpret(),
           "block_rows": row_scatter.BLOCK_ROWS, "rows": []}
    for d in map(int, args.widths.split(",")):
        for rows in map(int, args.rows.split(",")):
            for share in map(float, args.live.split(",")):
                row = probe(args.tokens, rows, d, args.groups, share,
                            args.calls, args.repeats, args.seed,
                            res["interpret"])
                res["rows"].append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
    text = json.dumps(res, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
