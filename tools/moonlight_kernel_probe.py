"""The two kernels moonlight-16b-a3b brought, timed alone on the chip at the
cell's sizes: where the form of attention at q, k 192 | v 128 and the claim
that the expert layer's cost follows the routed rows were settled (PERF.md,
PR 31).

Attention, 64 x 2048 (B 4, H 16), causal, bf16, forward AND backward
through flash_attention's custom_vjp under jax.value_and_grad, pads and
slices inside what is timed:
  native        q, k 192 and v 128 as they are (the kernels' own value width)
  padded-v      v zero-padded to 192, the output sliced back to 128 (exact)
  qk256         q, k zero-padded to 256, the scale 1/sqrt(192) explicit, v 128
  qk256-v256    everything padded to 256, the output sliced

The expert layer, T 8192 tokens, d 2048, width 1408, 8 held of 64, top 6:
ops/moe_ops.py::held_experts_part forward and backward at 0.5 x, 1 x and
2 x the expected rows (0.75 T), which fit the usual buffer (1.5 T rows), at
3 x and 3.9 x, which take the middle one (3 T), and at 6 x, which takes the
worst-case one (6 T rows), beside
  grouped-worst-buffer
                the same at 1 x with the worst-case buffer pinned: what of
                the cost follows the buffer and not the rows
and, under `experts-others`,
  xla-ragged-dot
                the same at 1 x with jax.lax.ragged_dot (XLA:TPU's own
                grouped kernel) in place of the Pallas one
  dense8        8 dense passes over all T tokens, masked (what a layer that
                ignores the routing pays)
and, under `tokens-from-rows` (PR 41), at the three expert cells' shapes
(T, d, width, held / total, k from benchmark/configs and benchmark/cells)
and 1 x and 2 x the expected rows: the layer forward + backward, and each
tokens <- rows site alone (_combine's forward: bf16 rows, fp32 weights;
_dispatch's backward: bf16 rows) with its worst difference from XLA's fp32
scatter-add.  The forms that lost to ops/moe_ops.py::tokens_from_rows (the
gather over all T x k assignments it replaced, other row tiles, the product
written out first, XLA's scatter-add) are in PERF.md 6, PR 41, with their
numbers; the tool no longer carries them.
One JSON line a row, all rows to --out (chiprun_out/moonlight_kernel_probe.json).

    chiprun --chips 1 -- python3 tools/moonlight_kernel_probe.py --seed 7
    JAX_PLATFORMS=cpu python3 tools/moonlight_kernel_probe.py --rehearse
`--rehearse` runs tiny shapes on whatever jax finds and exits 3: its times
are not the chip's.  One process holds the chip; it starts no child.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from flash_fwd_probe import PEAK_TFLOPS, _time_ms  # noqa: E402


def attention_rows(rng, B, H, S, dn, dr, dv, calls):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import flash_attention

    d = dn + dr
    q, k = (jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, H, S, dv)), jnp.bfloat16)
    scale = d ** -0.5

    def pad(x, to):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, to - x.shape[-1]),))

    def variant(qk_to, v_to):
        def loss(q, k, v):
            out = flash_attention(pad(q, qk_to), pad(k, qk_to), pad(v, v_to),
                                  causal=True, scale=scale)
            return jnp.sum(out[..., :dv].astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    # the causal half of the scores; a score costs the forward's two block
    # matmuls (q.k over d, p.v over dv) and the backward's five (scores and
    # dK and dQ over d, dP and dV over dv), at the widths the model has
    flops = 0.5 * B * H * S * S * 2 * (4 * d + 3 * dv)
    rows = []
    for name, qk_to, v_to in (("native", d, dv), ("padded-v", d, d),
                              ("qk256", 256, dv), ("qk256-v256", 256, 256)):
        if qk_to < d or v_to < dv:
            continue
        ms = _time_ms(variant(qk_to, v_to), (q, k, v), calls)
        rows.append({"what": "attention", "variant": name, "qk": qk_to,
                     "v": v_to, "shape": [B * H, S, d, dv],
                     "ms": round(ms, 4),
                     "share_of_peak": round(flops / ms / 1e9 / PEAK_TFLOPS,
                                            4)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def cell_shape(name, rehearse=False):
    """(T, d, f, held, total, k) of an expert cell, from its files."""
    from benchmark.harness import manifest

    cell = manifest.Cell(manifest.load_manifest(), name, rehearse=rehearse)
    cfg = cell.config
    held = cfg.get("n_routed_experts", cfg.get("num_experts"))
    return (int(cell.sizing["per_chip_batch"]) * cfg["max_length"],
            cfg["hidden_size"], cfg["moe_intermediate_size"], held,
            cfg["router_experts"], cfg["num_experts_per_tok"])


def _layer_operands(rng, T, d, f, held, k):
    """(x, weight, gate_w, up_w, down_w) of one expert layer, bf16 with
    fp32 gates."""
    import jax.numpy as jnp

    x = jnp.asarray(rng.standard_normal((T, d)), jnp.bfloat16)
    gate_w, up_w = (jnp.asarray(rng.standard_normal((held, d, f)) * 0.02,
                                jnp.bfloat16) for _ in range(2))
    down_w = jnp.asarray(rng.standard_normal((held, f, d)) * 0.02,
                         jnp.bfloat16)
    weight = jnp.asarray(rng.uniform(0.2, 0.6, (T, k)), jnp.float32)
    return x, weight, gate_w, up_w, down_w


def _routing(rng, T, k, held, total, mean_held):
    """idx [T, k]: a token's first n experts held ones, the rest not, n =
    floor(mean) or one more so that the mean is `mean_held`."""
    import numpy as np

    here = np.argsort(rng.random((T, held)), axis=1)[:, :k]
    away = held + np.argsort(rng.random((T, total - held)), axis=1)[:, :k]
    n = int(mean_held) + (rng.random(T) < mean_held - int(mean_held))
    return np.where(np.arange(k)[None, :] < n[:, None], here, away).astype(
        np.int32)


def tokens_from_rows_rows(rng, cell, shape, calls, shares):
    """The layer forward + backward and each tokens <- rows site alone, a
    row a share; a site's worst difference from the fp32 segment_sum beside
    its time."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import moe_ops

    T, d, f, held, total, k = shape
    operands = _layer_operands(rng, T, d, f, held, k)
    weight = operands[1]
    buffers = moe_ops.row_buffers(T, k, held, total)

    def layer(x, weight, gate_w, up_w, down_w, idx):
        return jnp.sum(jnp.sin(moe_ops.held_experts_part(
            x, idx, weight, gate_w, up_w, down_w, 0, total)))

    def combine(out, weight, order, pos, filled):
        return moe_ops._combine(out, weight, order, pos, filled, None)

    def dispatch_bwd(g, token, filled):
        return moe_ops.tokens_from_rows(g, token, filled, T)

    out = []
    for share in shares:
        idx = _routing(rng, T, k, held, total, share * k * held / total)
        routed = int(np.sum(idx < held))
        rows = int(next((b for b in buffers if routed <= b), buffers[-1]))
        # the sites' operands as held_experts_part makes them
        key = np.where(idx < held, idx, held).reshape(-1)
        order = np.argsort(key, kind="stable").astype(np.int32)
        pos = np.argsort(order).astype(np.int32)
        pos = jnp.asarray(
            np.where(idx.reshape(-1) < held, pos, T * k).reshape(T, k))
        order = jnp.asarray(order[:rows])
        token, filled = order // k, jnp.int32(routed)
        rows_bf16 = jnp.asarray(rng.standard_normal((rows, d)), jnp.bfloat16)
        held_rows = rows_bf16[:routed].astype(jnp.float32)
        w_row = jnp.take(weight.reshape(-1), order)
        base = {"cell": cell, "rows_over_expected": share,
                "routed_rows": routed, "row_buffer": rows,
                "shape": [T, d, f, held, total, k]}
        step = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4)))
        ms = _time_ms(step, operands + (jnp.asarray(idx),), calls)
        out.append(dict(base, what="layer", ms=round(ms, 4)))
        print(json.dumps(out[-1]), flush=True)
        # XLA's fp32 scatter-add of the fp32 products: what a site should give
        for site, fn, args, want in (
                ("combine", combine, (rows_bf16, weight, order, pos, filled),
                 w_row[:routed, None] * held_rows),
                ("dispatch_bwd", dispatch_bwd, (rows_bf16, token, filled),
                 held_rows)):
            want = np.asarray(jax.ops.segment_sum(want, token[:routed],
                                                  num_segments=T))
            fn = jax.jit(fn)
            ms = _time_ms(fn, args, calls)
            got = np.asarray(fn(*args).astype(jnp.float32))
            out.append(dict(base, what=site, ms=round(ms, 4),
                            max_abs_diff=float(np.max(np.abs(got - want))),
                            max_abs=float(np.max(np.abs(want)))))
            print(json.dumps(out[-1]), flush=True)
    return out


def expert_rows(rng, T, d, f, held, total, k, calls, shares, others):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import moe_ops

    x, weight, gate_w, up_w, down_w = _layer_operands(rng, T, d, f, held, k)
    expected = k * held / total

    def routing(mean_held):
        return jnp.asarray(_routing(rng, T, k, held, total, mean_held))

    buffers = moe_ops.row_buffers(T, k, held, total)

    def layer(x, weight, gate_w, up_w, down_w, idx, rows=None, engine=None):
        return jnp.sum(jnp.sin(moe_ops.held_experts_part(
            x, idx, weight, gate_w, up_w, down_w, 0, total, rows=rows,
            engine=engine)))

    def worst_only(*args):  # the same with the worst-case buffer pinned
        return layer(*args, rows=buffers[-1])

    def xla_ragged(*args):  # the same on XLA:TPU's own ragged_dot
        return layer(*args, engine="ragged_dot")

    def dense8(x, weight, gate_w, up_w, down_w, idx):
        y = jnp.zeros((T, d), jnp.float32)
        for e in range(held):
            g = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)
            h = (jax.nn.silu(jnp.matmul(
                x, gate_w[e], preferred_element_type=jnp.float32))
                * jnp.matmul(x, up_w[e],
                             preferred_element_type=jnp.float32))
            y = y + g[:, None] * jnp.matmul(
                h.astype(x.dtype), down_w[e],
                preferred_element_type=jnp.float32)
        return jnp.sum(jnp.sin(y))

    rows = []
    for name, fn, share in [("grouped", layer, s) for s in shares] + [
            ("grouped-worst-buffer", worst_only, 1.0)] + others * [
            ("xla-ragged-dot", xla_ragged, 1.0), ("dense8", dense8, 1.0)]:
        idx = routing(share * expected)
        routed = int(np.sum(np.asarray(idx) < held))
        step = jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4)))
        ms = _time_ms(step, (x, weight, gate_w, up_w, down_w, idx), calls)
        flops = 3 * 2.0 * routed * 3 * d * f    # forward + 2 x backward
        rows.append({"what": "experts", "variant": name,
                     "rows_over_expected": share, "routed_rows": routed,
                     "row_buffers": list(buffers),
                     "row_buffer": int(next(
                         (b for b in buffers if routed <= b), buffers[-1])
                         if fn is layer else buffers[-1]),
                     "ms": round(ms, 4),
                     "share_of_peak_routed_rows": round(
                         flops / ms / 1e9 / PEAK_TFLOPS, 4)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--what", default="attention,experts,experts-others")
    ap.add_argument("--shares", default="0.5,1,2,3,3.9,6",
                    help="the routed rows over the expected, a row each")
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "moonlight_kernel_probe.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    d0 = jax.devices()[0]
    if d0.platform != "tpu" and not args.rehearse:
        sys.stderr.write(f"probe: jax found no TPU ({d0.platform})\n")
        return 2
    rng = np.random.default_rng(args.seed)
    rows = []
    if "attention" in args.what:
        rows += attention_rows(rng, *((1, 2, 64, 16, 8, 16) if args.rehearse
                                      else (4, 16, 2048, 128, 64, 128)),
                               calls=args.calls)
    if "tokens-from-rows" in args.what:
        for cell in ("mellum-train-swa16k", "moonlight-train-ep8share",
                     "keye-train-dsa16k"):
            rows += tokens_from_rows_rows(
                rng, cell, cell_shape(cell, args.rehearse), args.calls,
                [float(s) for s in args.shares.split(",")])
    if "experts" in args.what.replace("tokens-from-rows", ""):
        rows += expert_rows(rng, *((64, 32, 24, 4, 32, 3) if args.rehearse
                                   else (8192, 2048, 1408, 8, 64, 6)),
                            calls=args.calls,
                            shares=[float(s) for s in args.shares.split(",")],
                            others="experts-others" in args.what)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"seed": args.seed, "device": d0.device_kind,
                   "rehearsal": bool(args.rehearse), "rows": rows}, f,
                  indent=1)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
