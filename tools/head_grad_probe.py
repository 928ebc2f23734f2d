"""A language model's head alone: the [rows, width] x [width, classes] matmul,
softmax + cross entropy, both gradients and an Adam update of the weight
(state donated), at the shapes the cells give it, in five formulations of
the loss; each row's loss carries a weight of its own, as an exit
distribution or a padding mask gives it.  Where the rule "one exponential pass, saved and pinned"
(ops/loss_ops.py::_hard_ce) was settled.

  ouro       16384 x 2048 x 49152                 ouro-train-loop4
  nmt        24576 x 512 x 32000, smooth_eps 0.1  transformer-train, -dp4
  moonlight  8192 x 2048 x 20480                  moonlight-train-ep8share

  autodiff        jax.nn.log_softmax, take_along_axis, jax's own gradient:
                  the op's Loss as it stood before PR 32
  autodiff-softmax  the same with the op's Softmax output beside it, given
                  the cotangent of zeros that a program which asks no
                  gradient of it hands the vjp: the op whole, as a cell ran it
  saved-unpinned  a custom_vjp that saves e = exp(x - max) in the logits'
                  dtype and its row sum; nothing holds XLA to the saved array
  saved-exp       the same with e behind lax.optimization_barrier: the op
                  as the tree has it (loss_ops._hard_ce itself)
  dlogits-pinned  saved-exp, and the backward's dLogits materialised once
                  behind a barrier too

On the chip, one JSON line a row: ms a step by the host's clock (calls
enqueued back to back, one wait), and from a profiler trace of five steps
the device ms a step of every operation over 0.2 ms.  `--chipless` compiles
the same steps here for the described v5e (core/aot_tpu.py) and prints the
compiler's transcendentals, temporaries and estimated cycles an operation
(1.5 GHz): no time is measured.  `--rehearse` runs tiny shapes on the CPU,
holds every formulation's gradients to autodiff's and exits 3.

A tool, run by no benchmark cell:
    chiprun --chips 1 -- python3 tools/head_grad_probe.py --seed 7
    JAX_PLATFORMS=cpu python3 tools/head_grad_probe.py --chipless
    JAX_PLATFORMS=cpu python3 tools/head_grad_probe.py --rehearse
One process holds the chip; it starts no child.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {
    # name: (rows, width, classes, smooth_eps)
    "ouro": (16384, 2048, 49152, 0.0),
    "nmt": (24576, 512, 32000, 0.1),
    "moonlight": (8192, 2048, 20480, 0.0),
}
REHEARSAL_SHAPES = {
    "ouro": (64, 32, 384, 0.0),
    "nmt": (96, 16, 250, 0.1),
    "moonlight": (32, 32, 160, 0.0),
}
FORMULATIONS = ("autodiff", "autodiff-softmax", "saved-unpinned", "saved-exp",
                "dlogits-pinned")
CLOCK_GHZ = 1.5
_ENTRY_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? (fusion|custom-call|"
                       r"convolution|copy)\(")
_KIND = re.compile(r"kind=(\w+)")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def _losses(jax, jnp, lo):
    """{formulation: ce(logits, lab, eps) -> loss [rows, 1]}"""

    def autodiff(logits, lab, eps, softmax_too=False):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, lab[..., None], axis=-1)
        if eps:
            loss = (1.0 - eps) * loss - eps * jnp.mean(
                logp, axis=-1, keepdims=True)
        loss = loss.astype(logits.dtype)
        if softmax_too:  # d/dSoftmax = zeros, an array as core/compiler.py's
            softmax = jnp.exp(logp).astype(logits.dtype)
            loss = loss + jnp.sum(softmax * jnp.zeros_like(softmax))
        return loss

    def saved(pin_e, pin_d):
        barrier = jax.lax.optimization_barrier

        @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
        def ce(logits, lab, eps):
            return lo._hard_ce_forward(logits, lab, eps, -100)[1]

        def fwd(logits, lab, eps):
            _, loss, e, s = lo._hard_ce_forward(logits, lab, eps, -100)
            return loss, (barrier(e) if pin_e else e, s, lab)

        def bwd(eps, res, g):
            d, _ = lo._hard_ce_bwd(eps, -100, False, res, (None, g))
            return (barrier(d) if pin_d else d), None

        ce.defvjp(fwd, bwd)
        return ce

    return {"autodiff": autodiff,
            "autodiff-softmax": functools.partial(autodiff, softmax_too=True),
            "saved-unpinned": saved(False, False),
            "saved-exp": lambda x, lab, eps: lo._hard_ce(
                x, lab, eps, -100, False)[1],
            "dlogits-pinned": saved(True, True)}


def _head_step(jax, jnp, ce, eps):
    """(w, m, v, h, lab, p) -> (w', m', v', dh, loss): bf16 states x the
    fp32 weight cast for the MXU, bf16 logits, the rows' losses weighted by
    p [rows, 1], Adam on w."""

    def step(w, m, v, h, lab, p):
        def loss_fn(w, h):
            logits = jnp.dot(h, w.astype(h.dtype),
                             preferred_element_type=jnp.float32)
            return jnp.sum(p * ce(logits.astype(h.dtype), lab,
                                  eps).astype(jnp.float32))

        loss, (gw, gh) = jax.value_and_grad(loss_fn, argnums=(0, 1))(w, h)
        m = 0.9 * m + 0.1 * gw
        v = 0.999 * v + 0.001 * gw * gw
        return w - 1e-4 * m / (jnp.sqrt(v) + 1e-8), m, v, gh, loss

    return step


def _entry_cycles(hlo: str, floor: int) -> list:
    """[(operation, kind, cycles)] of the entry computation's operations the
    compiler priced at `floor` cycles or more, in program order."""
    entry = hlo[hlo.index("\nENTRY "):]
    out = []
    for line in entry.splitlines():
        op, cycles = _ENTRY_OP.match(line), _CYCLES.search(line)
        if op and cycles and int(cycles.group(1)) >= floor:
            kind = _KIND.search(line)
            out.append((op.group(1), kind.group(1) if kind else op.group(2),
                        int(cycles.group(1))))
    return out


def _device_ms_by_op(trace, logdir, steps, floor_ms=0.2):
    ops = collections.Counter()
    for name, s, e in next(iter(trace.device_ops(trace.load(logdir))
                                .values()), []):
        ops[name] += (e - s) / 1e6 / steps
    return {n: round(ms, 3) for n, ms in ops.most_common() if ms >= floor_ms}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--shapes", default=None)
    ap.add_argument("--formulations", default=",".join(FORMULATIONS))
    ap.add_argument("--chipless", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/head_grad_probe.json")
    a = ap.parse_args()

    if a.chipless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import loss_ops as lo

    dev = jax.devices()[0]
    if not (a.rehearse or a.chipless) and dev.platform != "tpu":
        print("head_grad_probe: no TPU here (use --chipless or --rehearse "
              "on the CPU)", file=sys.stderr)
        return 2
    if a.chipless:  # a chip-less executable cannot be read back from a cache
        jax.config.update("jax_enable_compilation_cache", False)
        from paddle_tpu.core.aot_tpu import compile_tpu
    elif not a.rehearse:
        from benchmark.harness import trace
    shapes = REHEARSAL_SHAPES if a.rehearse else SHAPES
    losses = _losses(jax, jnp, lo)
    rows_out = []
    for name in (a.shapes.split(",") if a.shapes else shapes):
        rows, width, classes, eps = shapes[name]
        act = jnp.float32 if a.rehearse else jnp.bfloat16
        want = None
        for form in a.formulations.split(","):
            step = _head_step(jax, jnp, losses[form], eps)
            row = {"shape": name, "rows": rows, "width": width,
                   "classes": classes, "smooth_eps": eps,
                   "formulation": form, "seed": a.seed}
            if a.chipless:
                f32 = jax.ShapeDtypeStruct((width, classes), jnp.float32)
                c = compile_tpu(
                    step, f32, f32, f32,
                    jax.ShapeDtypeStruct((rows, width), act),
                    jax.ShapeDtypeStruct((rows,), jnp.int32),
                    jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                    donate_argnums=(0, 1, 2))
                cost = c.cost_analysis()
                priced = _entry_cycles(c.as_text(), 10 ** 6)
                total = sum(cycles for _, _, cycles in priced)
                row.update(
                    transcendentals_per_logit=round(
                        cost["transcendentals"] / (rows * classes), 3),
                    temp_gb=round(
                        c.memory_analysis().temp_size_in_bytes / 1e9, 3),
                    mcycles={f"{n} {k}": round(cy / 1e6, 2)
                             for n, k, cy in priced},
                    mcycles_sum=round(total / 1e6, 2),
                    ms_at_clock=round(total / CLOCK_GHZ / 1e6, 2))
                rows_out.append(row)
                print(json.dumps(row), flush=True)
                continue
            # fresh state a formulation: the step donates it
            r = np.random.RandomState(a.seed % (2 ** 32))
            w = jnp.asarray(r.randn(width, classes) / math.sqrt(width),
                            jnp.float32)
            m, v = jnp.zeros_like(w), jnp.zeros_like(w)
            h = jnp.asarray(r.randn(rows, width), act)
            lab = jnp.asarray(r.randint(0, classes, rows), jnp.int32)
            p = jnp.asarray(r.rand(rows, 1) / (0.5 * rows), jnp.float32)
            fn = jax.jit(step, donate_argnums=(0, 1, 2))
            w, m, v, gh, loss = fn(w, m, v, h, lab, p)  # compiles
            got = [np.asarray(x.astype(jnp.float32)) for x in (gh, m)]
            row["loss"] = float(loss)
            if want is None:
                want = got
            row["cosine_to_first"] = [round(float(
                np.vdot(g, t) / (np.linalg.norm(g) * np.linalg.norm(t))), 6)
                for g, t in zip(got, want)]
            if not a.rehearse:  # the CPU's time is no one's
                best = math.inf
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(a.calls):
                        w, m, v, gh, loss = fn(w, m, v, h, lab, p)
                    jax.block_until_ready(loss)
                    best = min(best, (time.perf_counter() - t0) / a.calls)
                row["ms_a_step"] = round(best * 1e3, 3)
                logdir = os.path.join("bench_out", "trace",
                                      f"head_grad_probe.{name}.{form}")
                trace.start(logdir)
                for _ in range(5):
                    w, m, v, gh, loss = fn(w, m, v, h, lab, p)
                jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                row["device_ms_by_op"] = _device_ms_by_op(trace, logdir, 5)
                row["device_ms"] = round(sum(
                    row["device_ms_by_op"].values()), 3)
            del w, m, v, gh
            rows_out.append(row)
            print(json.dumps(row), flush=True)
        if a.rehearse:
            worst = min(min(row["cosine_to_first"]) for row in rows_out
                        if row["shape"] == name)
            if not worst > 0.99999:
                print(f"head_grad_probe: at {name} a formulation's gradient "
                      f"has cosine {worst} to autodiff's", file=sys.stderr)
                return 1
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": bool(a.rehearse), "chipless": bool(a.chipless),
           "date": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
           "rows": rows_out}
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "chipless",
                                          "date")}))
    return 3 if a.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
