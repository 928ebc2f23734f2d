"""The flash forward alone, timed on the chip at the cells' shapes.

  ouro         32 x 2048 x 128 (B 2, H 16), causal           ouro-train-loop4
  nmt-decoder  768 x 256 x 64  (B 96, H 8), causal           transformer-train
  nmt-encoder  768 x 256 x 64, not causal, ragged k_lengths  transformer-train
  mellum-sliding  32 heads on 4 x 16384 x 128, window 1024   mellum-train-swa16k
  mellum-full     the same, no window                        mellum-train-swa16k

For each shape: this repo's kernel with its blocks pinned to 128 x 128 (what
every shape ran before the plan), the kernel at the blocks _plan_blocks gives
it, and jax.experimental.pallas.ops.tpu.flash_attention as a yardstick, once
at its shipped default blocks (all 128) and once at the plan's.  Where a
head is one block, `plan` is also the plan's batch-head rows a grid step
(_rows_per_step) and `--rows-per-step 1,2,4,...` pins each count in turn,
with and without the lse (how PR 53 settled it); the `bshd` rows are the
heads-last kernel on [B, S, H * D] operands (PR 57), where `--rows-per-step`
counts BATCH rows.  `--parent
FILE` times another commit's kernel beside them (`git show
<commit>:paddle_tpu/kernels/flash_attention.py > chip_scratch/...`).  `--sweep`
also pins every block pair a shape admits, which is how the plan's VMEM share
was settled.  A windowed shape (PR 59) runs the band (_pallas_band): `plan` is
_plan_band's block, `band-<b>` each candidate block pinned, with and without
the lse, beside the parent's block kernels under its window (`--parent`).
Prints ms a call and the TFLOP/s of the causal count (the (q, k) pairs the
mask lets through, the window's among them) and of the uncausal one (4 * B *
H * Sq * Sk * D), and writes the rows to chiprun_out/flash_fwd_probe.json.
The two 16k shapes are held to the reference on their first and last head
(a head's fp32 scores are 1 GB).

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/flash_fwd_probe.py --seed 7 [--sweep]
    JAX_PLATFORMS=cpu python3 tools/flash_fwd_probe.py --rehearse
`--rehearse` runs tiny shapes through the Pallas interpreter, skips the
yardstick (a TPU-only kernel) and exits 3: its times are not the chip's.
One process holds the chip; it starts no child.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {
    # name: (B, H, S, D, causal, ragged[, K/V heads, window])
    "ouro": (2, 16, 2048, 128, True, False),
    "nmt-decoder": (96, 8, 256, 64, True, False),
    "nmt-encoder": (96, 8, 256, 64, False, True),
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
BAND_BLOCKS = (256, 512)        # the band's candidates (--sweep: 128, 1024)
PEAK_TFLOPS = 197.0  # one v5e, bf16 (Google Cloud documentation, "TPU v5e")


def _time_ms(fn, args, calls):
    """ms a call: `calls` calls enqueued back to back, one wait; the best
    of three such rounds, after one call that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--shapes", default="ouro,nmt-decoder,nmt-encoder")
    ap.add_argument("--rows-per-step", default="", metavar="N,N,...",
                    help="batch-head rows a grid step, each pinned in turn "
                    "where a head is one block")
    ap.add_argument("--parent", metavar="FILE", help="kernels/"
                    "flash_attention.py of another commit (git show), timed "
                    "as it stands beside this tree's")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    parent = None
    if a.parent:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu.kernels._parent_flash_attention", a.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("flash_fwd_probe: no TPU here (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    shapes = REHEARSAL_SHAPES if a.rehearse else SHAPES
    rows = []
    for name in a.shapes.split(","):
        B, H, S, D, causal, ragged, *rest = shapes[name]
        G, window = rest or (H, None)
        rng = np.random.RandomState(a.seed % (2 ** 32))
        q, k, v = (jnp.asarray(rng.randn(B, n, S, D), jnp.bfloat16)
                   for n in (H, G, G))
        lengths = (rng.randint(S // 2, S + 1, size=B) if ragged
                   else np.full(B, S))
        klen = jnp.asarray(lengths, jnp.float32)
        scale = 1.0 / math.sqrt(D)
        uncausal = 4.0 * B * H * S * S * D
        visible = (fa._visible_pairs(S, S, True, window) if causal
                   else float(S * np.mean(lengths)))
        counted = 4.0 * B * H * visible * D

        def band_set(b, lse):
            return fa.band_fwd_working_set_bytes(
                b, fa._band(b, S // b, S // b, 0, window).n, D, S // b,
                "bfloat16", lse, None, H // G)

        if window is None:
            plan = fa._plan_blocks(S, S, D, q.dtype, causal, False)
        else:
            plan = 2 * (fa._plan_band(S, S, window, lambda b, n: band_set(
                b, True), True),)

        def ours(bq, bk, need_lse=False, **pins):
            return jax.jit(lambda q, k, v, klen: fa._pallas_flash(
                q, k, v, klen, causal, scale, block_q=bq, block_k=bk,
                interpret=a.rehearse, need_lse=need_lse, window=window,
                **pins)[0])

        def shipped(bq, bk):
            from jax.experimental.pallas.ops.tpu import flash_attention as jx

            bs = jx.BlockSizes(block_q=bq, block_k_major=bk, block_k=bk,
                               block_b=1)
            kvseg = jnp.where(jnp.arange(S)[None, :] < klen[:, None], 1, 2)
            seg = jx.SegmentIds(q=jnp.ones((B, S), jnp.int32),
                                kv=kvseg.astype(jnp.int32))
            return jax.jit(lambda q, k, v, klen: jx.flash_attention(
                q, k, v, segment_ids=seg if ragged else None, causal=causal,
                sm_scale=scale, block_sizes=bs))

        variants = []
        if parent is not None:      # the parent's kernel at the parent's plan
            pins = {} if window is None else {"window": window}
            variants += [("parent-plan" + ("+lse" if lse else ""), jax.jit(
                lambda q, k, v, klen, lse=lse: parent._pallas_flash(
                    q, k, v, klen, causal, scale, interpret=a.rehearse,
                    need_lse=lse, **pins)[0]), parent._plan_blocks(
                        S, S, D, q.dtype, causal, lse, None, *pins.values()))
                for lse in (False, True)]
        def planned_rows(lse):
            return fa._rows_per_step(
                B * H if G == H else 1, plan == (S, S) and window is None,
                lambda n: fa.fwd_working_set_bytes(
                    *plan, D, 1, "bfloat16", lse, None, n))

        # (label, call, blocks[, batch-head rows a grid step: 1 if absent])
        variants += [("pinned-128", ours(128, 128), (128, 128)),
                    ("plan", ours(*plan), plan, planned_rows(False)),
                    ("plan+lse", ours(*plan, need_lse=True), plan,
                     planned_rows(True))]
        if window is not None:      # the band at each candidate block
            blocks = BAND_BLOCKS + ((128, 1024) if a.sweep else ())
            variants += [(f"band-{b}" + ("+lse" if lse else ""),
                          ours(b, b, need_lse=lse), (b, b))
                         for b in blocks if b <= S for lse in (False, True)]
        if plan == (S, S) and window is None and G == H:
            # a head is one block: rows a grid step
            variants += [
                (f"rows-{n}" + ("+lse" if lse else ""),
                 ours(*plan, need_lse=lse, rows_per_step=n), plan, n)
                for n in map(int, filter(None, a.rows_per_step.split(",")))
                if (B * H) % n == 0 for lse in (False, True)]
        # heads-last: the operands as a model's projections write them, [B,
        # S, H * D], handed to the kernel as they lie (the heads-first rows
        # above take [B, H, S, D] ARGUMENTS, which a step never has: their
        # times hold the copy to the kernel's layout, PERF.md PR 53)
        last = [fa._heads_last(x) for x in (q, k, v)]
        if fa.takes_heads_last(*last, H, force=(
                "interpret" if a.rehearse else "pallas")):
            def heads_last(lse, **pins):
                return jax.jit(lambda q, k, v, klen: fa._pallas_flash_bshd(
                    q, k, v, klen, H, causal, scale, interpret=a.rehearse,
                    need_lse=lse, **pins)[0])

            rows_bshd = fa._heads_last_rows(B, S, S, H, D, "bfloat16",
                                            a.rehearse)
            variants += [("bshd" + ("+lse" if lse else ""), heads_last(lse),
                          plan, rows_bshd.forward if lse
                          else rows_bshd.forward_only)
                         for lse in (False, True)]
            variants += [
                (f"bshd-rows-{n}" + ("+lse" if lse else ""),
                 heads_last(lse, rows_per_step=n), plan, n)
                for n in map(int, filter(None, a.rows_per_step.split(",")))
                if B % n == 0 for lse in (False, True)]
        if a.sweep and window is None:
            lens = fa._block_lengths(S)
            variants += [(f"pinned-{bq}x{bk}", ours(bq, bk), (bq, bk))
                         for bq in lens for bk in lens
                         if (bq, bk) not in ((128, 128), plan)]
        if not a.rehearse and G == H:
            variants += [("jax-shipped-128", shipped(128, 128), (128, 128)),
                         ("jax-shipped-at-plan", shipped(*plan), plan)]
        # a head's fp32 scores are 1 GB at S 16384: the first and the last
        held = [0, H - 1] if 4 * B * H * S * S > 2 ** 32 else list(range(H))
        want = np.concatenate([np.asarray(fa._reference_attention(
            q[:, h:h + 1], k[:, h * G // H:h * G // H + 1],
            v[:, h * G // H:h * G // H + 1], causal, scale,
            k_lengths=klen.astype(jnp.int32), window=window
        ).astype(jnp.float32)) for h in held], axis=1)
        for label, fn, (bq, bk), *rows_per_step in variants:
            lse = label.endswith("lse")
            n = rows_per_step[0] if rows_per_step else 1
            bshd = label.startswith("bshd")
            row = {"shape": name, "bh": B * H, "s": S, "d": D,
                   "causal": causal, "variant": label, "block_q": bq,
                   "block_k": bk, "rows_per_step": n, "seed": a.seed,
                   "kv_heads": G, "window": window or 0,
                   "working_set_mb": round((
                       fa.fwd_working_set_bytes(
                           bq, bk, D, -(-S // bq), "bfloat16", lse, None, n,
                           H if bshd else 1)
                       if window is None or label.startswith("parent")
                       else band_set(bq, lse)) / 2 ** 20, 3)}
            args = (*last, klen) if bshd else (q, k, v, klen)
            try:
                got = fn(*args)
                got = np.asarray((fa._heads_first(got, H) if bshd
                                  else got)[:, np.asarray(held)].astype(
                                      jnp.float32))
                row["max_abs_err"] = float(np.max(np.abs(got - want)))
                if not a.rehearse:  # an interpreter's time is no one's
                    ms = _time_ms(fn, args, a.calls)
                    row.update(
                        ms_a_call=round(ms, 4),
                        tflops_causal_count=round(counted / ms / 1e9, 2),
                        tflops_uncausal_count=round(uncausal / ms / 1e9, 2),
                        share_of_peak=round(
                            counted / ms / 1e9 / PEAK_TFLOPS, 4))
            except Exception as e:  # a block pair Mosaic refuses is a row
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "date": time.strftime(
               "%Y-%m-%d %H:%M UTC", time.gmtime()), "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_fwd_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
