"""sha256 of a benchmark cell's step as it lowers for the TPU (StableHLO,
chip-less, at the cell's real sizes), whole and with what embeds source
paths cut (the kernels' payloads, the locations), and of the kernels
themselves (`kernels` of them: every tpu_custom_call's Mosaic module
printed without its locations, beside the rest of its config): how a PR
shows that it left the program alone.  Two checkouts lower to the same
step where their `sha256_cut` and `sha256_kernels` agree; `sha256` agrees
besides only from one path.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 tools/step_sha.py <cell> [<checkout, default this one>]

A one-chip cell goes through Executor.capture_program and core.aot_tpu (it
loads the TPU compiler: one such process at a time), a four-chip cell
through ParallelExecutor's own compile and jax.export on four virtual CPU
devices.  Seconds for the two oldest configurations, minutes for the three
newest (the startup program runs on the CPU).  Nothing runs on a chip."""
import base64
import hashlib
import json
import os
import re
import sys

name = sys.argv[1]
checkout = os.path.abspath(sys.argv[2] if len(sys.argv) > 2 else os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))
os.chdir(checkout)
sys.path.insert(0, checkout)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import paddle_tpu as fluid  # noqa: E402
from benchmark.harness import manifest  # noqa: E402
from paddle_tpu import flags  # noqa: E402
from paddle_tpu.core import aot_tpu  # noqa: E402

cell = manifest.Cell(manifest.load_manifest(), name)
cfg, mod = dict(cell.config), cell.config_module
rows = int(cell.sizing["per_chip_batch"]) * int(cell.chips)
spec = mod.build(cfg, 0)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
batch = mod.make_batch(cfg, spec, rows, 0)

if cell.chips == 1:
    with flags.tpu_trace_scope(True):
        compiled, feed_vals, state_vals, rng = exe.capture_program(
            feed=batch, fetch_list=[spec.loss])
        text = aot_tpu.trace_tpu(
            compiled.raw_fn, feed_vals, state_vals, rng,
            donate_argnums=(1,)).lower().as_text()
else:
    from paddle_tpu.core.executor import _RunPlan
    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    mesh = make_mesh(cell.sizing["mesh"], devices=jax.devices()[:cell.chips])
    pe = ParallelExecutor(loss_name=spec.loss.name, mesh=mesh)
    with flags.tpu_trace_scope(True):
        plan = _RunPlan(pe.program, sorted(batch), [spec.loss.name])
        compiled = pe._compile(plan)
        block0 = pe.program.desc.block(0)
        feed_sh, state_sh = plan.shardings

        def sds(v, sh):
            v = jax.numpy.asarray(v) if not hasattr(v, "dtype") else v
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)

        feeds = tuple(sds(v, s) for v, s in zip(
            plan.feed_values(batch, block0), feed_sh))
        state = plan.state_values(pe.scope, block0)
        key = plan.rng_value(pe.scope, pe.program)
        states = tuple(sds(v, s) for v, s in zip(state, state_sh[:-1]))
        exp = jax.export.export(compiled.fn, platforms=["tpu"])(
            feeds, states, sds(key, state_sh[-1]))
        text = exp.mlir_module()



def kernels(text):
    """Every kernel of the step as text that holds no source location: the
    custom call's config with its `body`, Mosaic's serialised module,
    parsed and printed without debug information."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True   # the serialised form's own
    found = []
    for quoted in re.findall(r'backend_config = "([^"\n]*)"', text):
        config = json.loads(re.sub(
            r'\\([0-9A-Fa-f]{2})', lambda m: chr(int(m.group(1), 16)),
            quoted)).get("custom_call_config")
        if config and "body" in config:
            with ctx:
                body = ir.Module.parse(base64.b64decode(config.pop("body")))
            found.append(json.dumps(config, sort_keys=True) + "\n"
                         + body.operation.get_asm(enable_debug_info=False))
    return found


found = kernels(text)
cut = re.sub(r'"[^"\n]{200,}"', '"<cut>"', text)
cut = re.sub(r'loc\([^\n]*', '', cut)
print(json.dumps({
    "cell": name, "checkout": checkout, "bytes": len(text),
    "sha256": hashlib.sha256(text.encode()).hexdigest(),
    "bytes_cut": len(cut),
    "sha256_cut": hashlib.sha256(cut.encode()).hexdigest(),
    "kernels": len(found),
    "sha256_kernels": hashlib.sha256(
        "\n====\n".join(found).encode()).hexdigest()}))
