"""keye-vl-2.0-30b-a3b's sparse attention alone on the chip at the cell's
shape (one sequence of 16384, 32 query heads over 4 key/value heads of 128,
an index of 16 x 64, top 2048, chunks of 512 queries, bf16): where the two
engines of ISSUE 33 were measured and the masked block one kept (PERF.md,
PR 33).

Rows, ms a call over the whole sequence (one layer):
  index            the index scores of every chunk (kernels/
                   sparse_attention.py::index_scores under the chunks' scan)
  index+select     and each row's exact top 2048
  forward          the op's forward: index, selection, attention, the heads'
                   summed probabilities, the index loss
  forward+backward jax.value_and_grad of (sum out + L_I): what a training
                   step runs once (forward) and once again (recomputed
                   forward + backward) a layer
  recomputed       forward+backward with the op inside a recomputed unit, as
                   a layer of the cell holds it, twice: `bare` under
                   jax.checkpoint (nothing but the inputs survives: the
                   backward runs the whole forward again) and `kept` under
                   core.compiler.rematerialised (the op's out, lse and thr
                   survive: PERF.md PR 34); bare - kept is what the second
                   forward costs a layer
  gather-chunk     the engine NOT kept, one chunk of 512 queries at the end
                   of the sequence, forward and backward by jax's own
                   gradient: each query gathers its 2048 chosen K and V rows
                   (scattered, as an index on seeded weights chooses them)
                   and attends to them, 8 query rows a key head; x 26.5
                   chunks' worth of chosen keys gives a sequence
  masked-chunk     the kept engine's attention kernels on the same chunk
                   and the same chosen keys, forward and backward
One JSON line a row, all rows to --out.

    chiprun --chips 1 -- python3 tools/keye_engine_probe.py --seed 7
    JAX_PLATFORMS=cpu python3 tools/keye_engine_probe.py --rehearse
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


def chosen_pairs(S: int, topk: int) -> int:
    """Query-key pairs the selection keeps: sum over t of min(t + 1, topk)."""
    head = min(S, topk)
    return head * (head + 1) // 2 + (S - head) * topk


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--kv-chunk", type=int, default=None)
    ap.add_argument("--what", default="index,select,forward,backward,"
                    "recomputed,gather,masked")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/keye_engine_probe.json")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.compiler import rematerialised
    from paddle_tpu.kernels import sparse_attention as sa

    if a.rehearse:
        S, H, G, D, Hi, Di, topk, tq, tk = 64, 4, 2, 16, 3, 8, 16, 16, 16
        engine = "xla"
    else:
        S, H, G, D, Hi, Di, topk, tq, tk = (16384, 32, 4, 128, 16, 64, 2048,
                                            512, 512)
        engine = None
    tk = a.kv_chunk or tk       # sparse_attention's plan blocks two of them
    tkb = sa.plan(S, tq, tk)["kv_block"]
    what = set(a.what.split(","))
    rng = np.random.default_rng(a.seed)
    dev = jax.devices()[0]

    def normal(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q, k, v = normal(1, H, S, D), normal(1, G, S, D), normal(1, G, S, D)
    qi, ki = normal(1, Hi, S, Di), normal(1, S, Di)
    w = normal(1, S, Hi, dtype=jnp.float32) * (Hi * Di) ** -0.5
    scale = D ** -0.5
    n = S // tq
    rows = []

    def row(name, ms, flops=None, **more):
        r = {"row": name, "ms": round(ms, 3), "device": dev.device_kind,
             "seed": a.seed, "S": S, "kv_block": tkb, **more}
        if flops:
            r["counted_tflops"] = round(flops / 1e12, 3)
            r["share_of_peak"] = round(flops / ms / 1e9 / PEAK_TFLOPS, 4)
        rows.append(r)
        print(json.dumps(r), flush=True)

    def over_chunks(body):
        """sum over the chunks' scan of body(c, qi_c, w_c) -> scalar."""
        def f(qi, ki, w):
            def step(acc, xs):
                c, qi_c, w_c = xs
                return acc + body(c, qi_c, ki, w_c), None
            return jax.lax.scan(step, jnp.float32(0), (
                jnp.arange(n, dtype=jnp.int32), sa._chunks(qi, 1, n),
                sa._chunks(w, 0, n)))[0]
        return jax.jit(f)

    if "index" in what:
        fn = over_chunks(lambda c, qi_c, ki, w_c: jnp.sum(
            sa.index_scores(qi_c, ki, w_c)))
        row("index", _time_ms(fn, (qi[0], ki[0], w[0]), a.calls),
            2.0 * Hi * Di * S * S)
    if "select" in what:
        fn = over_chunks(lambda c, qi_c, ki, w_c: jnp.sum(
            sa._chunk_mask(qi_c, ki, w_c, c * tq, topk, tkb,
                           engine or "pallas")[1],
            dtype=jnp.float32))
        ms = _time_ms(fn, (qi[0], ki[0], w[0]), a.calls)
        row("index+select", ms, chosen=float(fn(qi[0], ki[0], w[0])),
            chosen_expected=chosen_pairs(S, topk))

    def op(*args):
        out, kl = sa.sparse_attention(*args, topk=topk, scale=scale,
                                      q_chunk=tq, kv_chunk=tk,
                                      force="jax" if engine else "auto")
        return jnp.sum(out.astype(jnp.float32)) + kl

    pairs = chosen_pairs(S, topk)
    attend = 2.0 * 2 * H * D * pairs        # QK^T and PV over chosen keys
    if "forward" in what:
        row("forward", _time_ms(jax.jit(op), (q, k, v, qi, ki, w), a.calls),
            attend)
    if "backward" in what:
        fn = jax.jit(jax.value_and_grad(op, argnums=tuple(range(6))))
        row("forward+backward", _time_ms(fn, (q, k, v, qi, ki, w), a.calls),
            3.5 * attend)

    if "recomputed" in what:
        for name, unit in (("bare", jax.checkpoint(op)),
                           ("kept", rematerialised(op))):
            fn = jax.jit(jax.value_and_grad(unit, argnums=tuple(range(6))))
            row("recomputed-" + name,
                _time_ms(fn, (q, k, v, qi, ki, w), a.calls), 3.5 * attend,
                kept_bytes=sa.kept_bytes(q) if name == "kept" else 0)

    # one chunk, the last of the sequence, under both engines: the same
    # chosen keys (scattered uniformly over the causal ones)
    first = S - tq
    picks = np.stack([np.sort(rng.choice(first + i + 1, size=min(
        topk, first + i + 1), replace=False)) for i in range(tq)])
    idx = jnp.asarray(picks, jnp.int32)                       # [tq, topk]
    mask = jnp.zeros((tq, S), bool).at[
        jnp.arange(tq)[:, None], idx].set(True)
    q_c = q[0, :, first:]
    chunk_flops = 3.5 * 2.0 * 2 * H * D * tq * picks.shape[1]

    def gather_attend(q_c, k, v):
        kg = jnp.take(k, idx, axis=1)                  # [G, tq, topk, D]
        vg = jnp.take(v, idx, axis=1)
        qg = q_c.reshape(G, H // G, tq, D)
        s = jnp.einsum("grtd,gtsd->grts", qg, kg,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("grts,gtsd->grtd", p.astype(vg.dtype), vg,
                         preferred_element_type=jnp.float32)
        return jnp.sum(out)

    if "gather" in what:
        fn = jax.jit(jax.value_and_grad(gather_attend, argnums=(0, 1, 2)))
        row("gather-chunk", _time_ms(fn, (q_c, k[0], v[0]), a.calls),
            chunk_flops, chunks_a_sequence=round(pairs / (tq * topk), 2))
    if "masked" in what:
        eng = "xla" if a.rehearse else "pallas"

        def masked(q_c, k, v, do_c):
            if eng == "xla":
                out, lse, _ = sa._xla_attend(q_c, k, v, mask, scale)
                return sa._xla_attend_bwd(q_c, k, v, mask, scale, do_c)[:3]
            out, lse, _ = sa._pallas_attend(q_c, k, v, mask, scale, tkb,
                                            False)
            return sa._pallas_attend_bwd(q_c, k, v, mask, scale, do_c, out,
                                         lse, tkb, False)[:3]

        row("masked-chunk", _time_ms(jax.jit(masked), (
            q_c, k[0], v[0], jnp.ones_like(q_c)), a.calls), chunk_flops,
            chunks_a_sequence=n, note="the last chunk: every causal block "
            "lives; a sequence's chunks see half as many on average")

    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
