"""The flash backward alone, timed on the chip: both engines at the cells'
shapes and along a ladder of sequence lengths, which is where the rule that
picks the engine from the shape (kernels/flash_attention.py::_bwd_plan) was
settled.

  ouro         32 x 2048 x 128 (B 2, H 16), causal           ouro-train-loop4
  nmt-decoder  768 x 256 x 64  (B 96, H 8), causal           transformer-train
  nmt-encoder  768 x 256 x 64, not causal, ragged k_lengths  transformer-train
  s<S>-d<D>    the ladder: S 256 / 384 / 512 / 1024 / 2048 at head 64 and
               128, causal, B * S held at 24576 (d 64) or 4096 (d 128) tokens
  mellum-sliding  32 heads on 4 x 16384 x 128, window 1024   mellum-train-swa16k
  mellum-full     the same, no window                        mellum-train-swa16k

For each shape, one JSON line a row:
  xla          jax.vjp of the reference formulation given (q, k, v, dO): the
               recompute backward, its score planes through HBM
  pallas       _flash_bwd_kernel given (q, k, v, O, lse, dO) at the blocks
               _plan_bwd_blocks gives the shape
  step-xla / step-pallas
               forward AND backward through flash_attention's custom_vjp
               under jax.value_and_grad (what a training step runs: the
               forward emits lse only for the Pallas backward) with the
               engine held to one side, and
  step-rule    the same with the engine the rule reads off the shape
Where a head is one block `pallas` and the `step-*` rows run at the batch-head
rows a grid step that _rows_per_step gives, and `--rows-per-step 1,2,4,...`
pins each count in turn: `pallas-rows-N` the backward kernel alone,
`step-pallas-rows-N` forward and backward with both held to N (PR 53).
`bshd` / `step-bshd` are the heads-last kernels on [B, S, H * D] operands
(PR 57: D = rowsum(dO * O) inside the kernel, O its operand; the heads-first
rows take [B, H, S, D] ARGUMENTS, which a step never has, and their times
hold the copy to the kernel's layout); `--rows-per-step` counts BATCH rows
there.
A windowed shape (PR 59) runs the band, ONE call of _band_bwd_kernel:
`pallas` at _plan_band's block, `band-<b>` each candidate pinned, beside the
parent's chunked calls and their glue (`--parent`: `parent-pallas`,
`step-parent`).  The two 16k shapes have no `xla` rows (34 GB of scores) and
are held to the reference, a head at a time, on their first K/V head's group.
`--sweep` also pins every block pair the shape admits whose working set is
under 1.5 x the plan's share; `--parent FILE` times another commit's
`_pallas_flash_bwd` as it stands (`git show <commit>:paddle_tpu/kernels/
flash_attention.py > chip_scratch/...`).  Prints ms a call and the TFLOP/s of
the causal count at 2.5 x the forward's FLOPs (the five block matmuls the
kernel runs: the recompute of the scores among them), and writes the rows
to `--out` (chiprun_out/flash_bwd_probe.json).

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/flash_bwd_probe.py --seed 7 [--sweep]
    JAX_PLATFORMS=cpu python3 tools/flash_bwd_probe.py --rehearse
`--rehearse` runs tiny shapes through the Pallas interpreter and exits 3:
its times are not the chip's.  One process holds the chip; it starts no
child.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import importlib.util
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from flash_fwd_probe import BAND_BLOCKS, PEAK_TFLOPS, _time_ms  # noqa: E402

SHAPES = {
    # name: (B, H, S, D, causal, ragged)
    "ouro": (2, 16, 2048, 128, True, False),
    "nmt-decoder": (96, 8, 256, 64, True, False),
    "nmt-encoder": (96, 8, 256, 64, False, True),
    "s256-d128": (16, 16, 256, 128, True, False),
    "s384-d64": (64, 8, 384, 64, True, False),
    "s384-d128": (11, 16, 384, 128, True, False),
    "s512-d64": (48, 8, 512, 64, True, False),
    "s512-d128": (8, 16, 512, 128, True, False),
    "s1024-d64": (24, 8, 1024, 64, True, False),
    "s1024-d128": (4, 16, 1024, 128, True, False),
    "s2048-d64": (12, 8, 2048, 64, True, False),
    # (..., K/V heads, window): run on request (--shapes)
    "mellum-sliding": (1, 32, 16384, 128, True, False, 4, 1024),
    "mellum-full": (1, 32, 16384, 128, True, False, 4, None),
}
REHEARSAL_SHAPES = {
    "ouro": (1, 2, 512, 128, True, False),
    "nmt-decoder": (2, 2, 256, 64, True, False),
    "nmt-encoder": (2, 2, 256, 64, False, True),
    "mellum-sliding": (1, 4, 512, 64, True, False, 2, 128),
    "mellum-full": (1, 4, 512, 64, True, False, 2, None),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--shapes", default=None)
    ap.add_argument("--rows-per-step", default="", metavar="N,N,...",
                    help="batch-head rows a grid step, each pinned in turn "
                    "where a head is one block")
    ap.add_argument("--out", default="chiprun_out/flash_bwd_probe.json")
    ap.add_argument("--parent", metavar="FILE", help="kernels/"
                    "flash_attention.py of another commit (git show), its "
                    "_pallas_flash_bwd timed as it stands")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    from paddle_tpu.kernels import engine
    parent = None
    if a.parent:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu.kernels._parent_flash_attention", a.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("flash_bwd_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    shapes = REHEARSAL_SHAPES if a.rehearse else SHAPES
    rule_threshold = fa._BWD_PALLAS_MIN_BLOCK_SCORES
    rows = []
    for name in (a.shapes.split(",") if a.shapes else [
            n for n in shapes if not n.startswith("mellum")]):
        B, H, S, D, causal, ragged, *rest = shapes[name]
        G, window = rest or (H, None)
        rng = np.random.RandomState(a.seed % (2 ** 32))
        q, k, v, g = (jnp.asarray(rng.randn(B, n, S, D), jnp.bfloat16)
                      for n in (H, G, G, H))
        lengths = (rng.randint(S // 2, S + 1, size=B) if ragged
                   else np.full(B, S))
        klen = jnp.asarray(lengths, jnp.float32)
        scale = 1.0 / math.sqrt(D)
        visible = (fa._visible_pairs(S, S, True, window) if causal
                   else float(S * np.mean(lengths)))
        counted = 2.5 * 4.0 * B * H * visible * D
        plan = fa._bwd_plan(S, S, D, q.dtype, causal, window=window,
                            bh=fa._packable_rows(q, k), group=H // G,
                            site_bh=B * H)
        force = "interpret" if a.rehearse else "pallas"
        windowed = {} if window is None else {"window": window}
        big = 4 * B * H * S * S > 2 ** 32   # no [B, H, S, S] fp32 scores

        def reference(q, k, v):
            return fa._reference_attention(
                q, k, v, causal, scale, k_lengths=klen.astype(jnp.int32),
                window=window)

        out, lse = jax.jit(lambda q, k, v: fa._pallas_flash(
            q, k, v, klen, causal, scale, interpret=a.rehearse,
            **windowed))(q, k, v)

        def kernels(module, **pins):
            return jax.jit(lambda q, k, v, g: module._pallas_flash_bwd(
                q, k, v, klen, out, lse, g, causal, scale,
                interpret=a.rehearse, **windowed, **pins))

        def step(threshold, rows_per_step=None, module=fa):
            """fwd + bwd with the rule's threshold held at `threshold` (and
            both kernels' rows a grid step at `rows_per_step`, where given)
            while the call is traced and compiled; the loss is returned too,
            or the forward of the XLA engine (nothing of it is a residual)
            is dead code."""
            def loss(q, k, v, g):
                o = module.flash_attention(q, k, v, causal=causal,
                                           k_lengths=klen, force=force,
                                           **windowed)
                return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))

            planned = fa._rows_per_step
            fa._BWD_PALLAS_MIN_BLOCK_SCORES = threshold
            if rows_per_step is not None:
                fa._rows_per_step = lambda bh, one_block, working_set: (
                    rows_per_step if one_block and bh % rows_per_step == 0
                    else 1)
            fa._bwd_chunk_rows.cache_clear()    # the rule's answer is cached
            try:
                fn = jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1, 2))).lower(q, k, v, g).compile()
            finally:
                fa._BWD_PALLAS_MIN_BLOCK_SCORES = rule_threshold
                fa._rows_per_step = planned
                fa._bwd_chunk_rows.cache_clear()
            return lambda *args: fn(*args)[1]

        xla = jax.jit(lambda q, k, v, g: jax.vjp(reference, q, k, v)[1](g))
        pair = (plan["block_q"], plan["block_k"])
        counts = [n for n in map(int, filter(None, a.rows_per_step.split(",")))
                  if pair == (S, S) and (B * H) % n == 0]
        planned = plan["rows_per_step"]
        # (label, call, blocks[, batch-head rows a grid step: 1 if absent]);
        # a `step-*` call is built where it is timed, so that a compile
        # Mosaic refuses is a row like any other
        variants = [] if big else [("xla", xla, (None, None))]
        variants.append(("pallas", kernels(fa), pair, planned))
        variants += [(f"pallas-rows-{n}", kernels(fa, rows_per_step=n), pair,
                      n) for n in counts]
        if window is not None:      # the band at each candidate block
            blocks = BAND_BLOCKS + ((128, 1024) if a.sweep else ())
            variants += [(f"band-{b}", kernels(fa, block_q=b, block_k=b),
                          (b, b)) for b in blocks if b <= S]
        if parent is not None:
            variants += [
                ("parent-pallas", kernels(parent), (lse.shape[2], 128)),
                ("step-parent", lambda: step(rule_threshold, module=parent),
                 (None, None))]
        if not (a.rehearse or big):   # "interpret" keeps the Pallas backward
            variants.append(("step-xla", lambda: step(2 ** 62),
                             (None, None)))
        variants += [("step-pallas", lambda: step(0), pair, planned),
                     ("step-rule:" + plan["engine"],
                      lambda: step(rule_threshold), pair, planned)]
        variants += [(f"step-pallas-rows-{n}",
                      functools.partial(step, 0, n), pair, n) for n in counts]
        # heads-last (PR 57): [B, S, H * D] operands as the projections
        # write them; D = rowsum(dO * O) is the kernel's own work
        last = [fa._heads_last(x) for x in (q, k, v, g, out)]
        lse_rows = lse.reshape(B, H, -1)
        if fa.takes_heads_last(*last[:3], H, force=force):
            def heads_last(**pins):
                return jax.jit(
                    lambda q, k, v, g, out: fa._pallas_flash_bwd_bshd(
                        q, k, v, klen, out, lse_rows, g, H, causal, scale,
                        interpret=a.rehearse, **pins))

            def step_heads_last():
                def loss(q, k, v, g):
                    o = fa.flash_attention(q, k, v, causal=causal,
                                           k_lengths=klen, force=force,
                                           heads=H)
                    return jnp.sum(o.astype(jnp.float32)
                                   * g.astype(jnp.float32))

                fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
                return lambda *args: fn(*args)[1]

            rows_bshd = fa._heads_last_rows(B, S, S, H, D, "bfloat16",
                                            a.rehearse).backward
            variants += [("bshd", heads_last(), pair, rows_bshd),
                         ("step-bshd", step_heads_last, pair, rows_bshd)]
            variants += [
                (f"bshd-rows-{n}", heads_last(rows_per_step=n), pair, n)
                for n in map(int, filter(None, a.rows_per_step.split(",")))
                if B % n == 0]
        if a.sweep and window is None:
            lens = fa._block_lengths(S)
            variants += [
                (f"pallas-{bq}x{bk}", kernels(fa, block_q=bq, block_k=bk),
                 (bq, bk)) for bq in lens for bk in lens
                if (bq, bk) != pair and fa.bwd_working_set_bytes(
                    bq, bk, D, -(-S // bq), "bfloat16")
                <= 1.5 * engine.PLAN_VMEM_BUDGET]
        f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
        group = H // G

        def first_group(grads):
            """dq of the first K/V head's query heads, its dk and dv."""
            dq, dk, dv = grads
            return dq[:, :group], dk[:, :1], dv[:, :1]

        if big:     # a head at a time: a head's fp32 scores are 1 GB
            one = jax.jit(lambda q, k, v, g: jax.vjp(reference, q, k, v)[1](g))
            heads = [one(f32[0][:, h:h + 1], f32[1][:, :1], f32[2][:, :1],
                         f32[3][:, h:h + 1]) for h in range(group)]
            want = [np.concatenate([np.asarray(x[0]) for x in heads], 1),
                    sum(np.asarray(x[1]) for x in heads),
                    sum(np.asarray(x[2]) for x in heads)]
        else:
            want = [np.asarray(x) for x in xla(*f32)]
        for label, fn, (bq, bk), *rows_per_step in variants:
            row = {"shape": name, "bh": B * H, "s": S, "d": D,
                   "causal": causal, "variant": label, "block_q": bq,
                   "block_k": bk, "seed": a.seed, "kv_heads": G,
                   "window": window or 0}
            bshd = "bshd" in label
            if bq is not None:
                n = rows_per_step[0] if rows_per_step else 1
                row["rows_per_step"] = n
                band = window is not None and not label.startswith("parent")
                row["working_set_mb"] = round((
                    fa.band_bwd_working_set_bytes(
                        bq, fa._band(bq, S // bq, S // bq, 0, window).n, D,
                        S // bq, "bfloat16", None, group) if band
                    else fa.bwd_working_set_bytes(
                        bq, bk, D, -(-S // bq), "bfloat16", None, n,
                        H if bshd else 1)) / 2 ** 20, 3)
            args = (q, k, v, g)
            if bshd:    # the kernel alone also takes O; the step makes it
                args = last[:4] if label.startswith("step-") else last
            try:
                if label.startswith("step-"):
                    fn = fn()
                got = fn(*args)
                got = [np.asarray((fa._heads_first(x, H) if bshd
                                   else x).astype(jnp.float32))
                       for x in (first_group(got) if big else got)]
                row["max_abs_err"] = max(float(np.max(np.abs(x - w)))
                                         for x, w in zip(got, want))
                if not a.rehearse:  # an interpreter's time is no one's
                    ms = _time_ms(fn, args, a.calls)
                    row.update(
                        ms_a_call=round(ms, 4),
                        tflops_causal_count=round(counted / ms / 1e9, 2),
                        share_of_peak=round(
                            counted / ms / 1e9 / PEAK_TFLOPS, 4))
            except Exception as e:  # a block pair Mosaic refuses is a row
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
