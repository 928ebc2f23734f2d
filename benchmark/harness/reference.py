"""Holds a training cell's first step to its configuration's plain reference
(benchmark/configs/<config>.reference.py): the loss the program fetched and
the gradient its optimizer saw, against loss and gradient of the reference in
fp32 on the same parameters and the same batch, within the tolerances of the
configuration file's `reference` group.

The program's gradient is read from the optimizer's own state after the first
step, so the measured program is not changed: Adam's first moment starts at 0
and is (1 - beta1) x gradient after one step, Momentum's velocity is the
gradient."""

from __future__ import annotations

import functools
import math
import os

from . import manifest

ADAM_BETA1 = 0.9      # fluid.optimizer.AdamOptimizer's default


class FirstStep:
    """Made after the start-up program and before the first step (it copies
    the parameters, which the step donates); `compare` after that step."""

    def __init__(self, cell, spec, place_on=None):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as fluid

        self.cell, self.spec = cell, spec
        self.tol = cell.config["reference"]
        self.module = manifest.load_py(os.path.join(
            manifest.BENCH, "configs",
            cell.entry["config"] + ".reference.py"))
        params = fluid.default_main_program().all_parameters()
        self.trainable = frozenset(p.name for p in params if p.trainable)
        scope = fluid.global_scope()
        self.params = {p.name: jnp.copy(scope.find_var(p.name))
                       for p in params}
        if place_on is not None:
            self.params = jax.device_put(self.params, place_on)

    def program_gradient(self) -> dict:
        import paddle_tpu as fluid

        scope = fluid.global_scope()
        opt = self.cell.config["optimizer"]["name"]
        state, scale = {"adam": ("moment1", 1.0 / (1.0 - ADAM_BETA1)),
                        "momentum": ("velocity", 1.0)}[opt]
        return {name: scope.find_var(f"{name}_{state}_0") * scale
                for name in sorted(self.trainable)}

    def compare(self, first_loss: float, batch: dict, per_chip: int):
        """(what was measured, the list of problems)."""
        import jax
        import jax.numpy as jnp

        micro = max(1, per_chip // int(self.tol.get("rows_per_part",
                                                    per_chip)))
        ref = jax.jit(functools.partial(
            self.module.loss_and_grad, cfg=self.cell.config,
            feed_names=tuple(self.spec.feed_names),
            trainable=self.trainable, micro=micro))
        ref_loss, ref_grad = ref(self.params, batch)

        @jax.jit
        def products(a, b):
            def f32(x):
                return x.astype(jnp.float32)
            return {k: (jnp.vdot(f32(a[k]), f32(b[k])),
                        jnp.vdot(f32(a[k]), f32(a[k])),
                        jnp.vdot(f32(b[k]), f32(b[k]))) for k in b}

        prods = {k: tuple(float(x) for x in v) for k, v in
                 products(self.program_gradient(), ref_grad).items()}
        self.params = None
        found = judge(float(first_loss), float(ref_loss), prods)
        return found, problems(found, self.tol)


def judge(loss: float, ref_loss: float, prods: dict) -> dict:
    """prods: {parameter: (g.r, g.g, r.r)} of the program's gradient g and
    the reference's r.  The worst single parameter is taken among those
    that hold at least 1% of the reference gradient's norm."""
    dot = sum(p[0] for p in prods.values())
    gg = sum(p[1] for p in prods.values())
    rr = sum(p[2] for p in prods.values())
    worst_name, worst = "", 1.0
    for name, (_, g2, r2) in sorted(prods.items()):
        if r2 < 1e-4 * rr:
            continue
        ratio = math.sqrt(g2 / r2)
        far = max(ratio, 1.0 / ratio) if ratio > 0 else math.inf
        if far > worst:
            worst_name, worst = name, far
    return {
        "loss": loss, "reference_loss": ref_loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_cos": dot / math.sqrt(gg * rr) if gg > 0 and rr > 0 else 0.0,
        "grad_norm_ratio": math.sqrt(gg / rr) if rr > 0 else math.inf,
        "param_norm_far": worst, "param_norm_far_name": worst_name,
    }


def compared(found: dict, tol: dict) -> dict:
    """{short name: [the number compared, its limit]} of `problems`' four
    comparisons, for the run's last lines and its result line."""
    return {
        "loss_rel": [found["loss_rel"], tol["loss_rtol"]],
        "grad_cos_min": [found["grad_cos"], tol["grad_cos_min"]],
        "grad_norm_off_1": [abs(found["grad_norm_ratio"] - 1.0),
                            tol["grad_norm_rtol"]],
        "param_norm_far": [found["param_norm_far"],
                           tol["param_norm_factor"]],
    }


def problems(found: dict, tol: dict) -> list:
    out = []
    if not found["loss_rel"] <= tol["loss_rtol"]:
        out.append(f"first step's loss {found['loss']} against the "
                   f"reference's {found['reference_loss']}: off by "
                   f"{found['loss_rel']:.2e}, over {tol['loss_rtol']}")
    if not found["grad_cos"] >= tol["grad_cos_min"]:
        out.append(f"first step's gradient against the reference's: cosine "
                   f"{found['grad_cos']:.4f}, under {tol['grad_cos_min']}")
    if not abs(found["grad_norm_ratio"] - 1.0) <= tol["grad_norm_rtol"]:
        out.append(f"first step's gradient norm over the reference's: "
                   f"{found['grad_norm_ratio']:.4f}, further from 1 than "
                   f"{tol['grad_norm_rtol']}")
    if not found["param_norm_far"] <= tol["param_norm_factor"]:
        out.append(f"gradient norm of {found['param_norm_far_name']} is a "
                   f"factor {found['param_norm_far']:.2f} from the "
                   f"reference's, over {tol['param_norm_factor']}")
    return out
