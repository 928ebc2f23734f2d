"""Runner of kind `train`: one synthetic batch staged on the device(s) once,
warm-up steps (the first compiles), then steady steps for --seconds.  One
chip goes through fluid.Executor, several through ParallelExecutor on the
cell's mesh."""

from __future__ import annotations

import os
import time

from . import device as _device
from . import reference, stats, trace


def run(cell, args, devices, t_start: float) -> dict:
    import jax
    import numpy as np
    import paddle_tpu as fluid

    tpu = devices[0].platform == "tpu"
    n = len(devices)
    cfg, mod = cell.config, cell.config_module
    per_chip = int(cell.sizing["per_chip_batch"])
    # the mix says how many per-chip batches a step takes, the cell's chips
    # have to be as many
    batch_size = per_chip * int(cell.traffic["per_chip_batches"])
    if batch_size != per_chip * n:
        raise ValueError(
            f"cell {cell.name}: mix {cell.entry['traffic']} takes "
            f"{cell.traffic['per_chip_batches']} per-chip batches a step, "
            f"the cell has {n} chips")
    counter = stats.CompileCounter()

    spec = mod.build(cfg, args.seed)
    place = fluid.TPUPlace() if tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    host_batch = mod.make_batch(cfg, spec, batch_size, args.seed)
    replicated = None
    if n == 1:
        batch = jax.device_put(host_batch, devices[0])

        def step():
            return exe.run(feed=batch, fetch_list=[spec.loss])[0]
    else:
        from paddle_tpu.parallel import ParallelExecutor, make_mesh

        mesh = make_mesh(cell.sizing["mesh"], devices=devices)
        pe = ParallelExecutor(loss_name=spec.loss.name, mesh=mesh)
        sharded = mesh.batch_sharding()
        batch = jax.device_put(host_batch, sharded)
        replicated = jax.sharding.NamedSharding(
            sharded.mesh, jax.sharding.PartitionSpec())

        def step():
            return pe.run(feed=batch, fetch_list=[spec.loss])[0]

    def one_step(name="bench.step"):
        with jax.profiler.TraceAnnotation(name):
            # np.asarray waits for the device: the step ends when the loss
            # it fetched is on the host
            return float(np.ravel(np.asarray(step()))[0])

    # the first step, which compiles, is held to the plain reference
    first = reference.FirstStep(cell, spec, place_on=replicated)
    memory = _device.StepMemory(devices)
    memory.before_first_step()
    warm_losses = [one_step("bench.warm_step")]
    memory.after_first_step()
    found, problems = first.compare(warm_losses[0], batch, per_chip)
    numbers = reference.compared(found, first.tol)
    del first
    print(f"[bench] first step against the reference: {found}", flush=True)
    warm_losses += [one_step("bench.warm_step")
                    for _ in range(int(cell.traffic["warm_steps"]) - 1)]

    # the measured window: whole steps until --seconds have gone
    compiles_before = counter.count
    losses = []
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    while True:
        losses.append(one_step())
        if time.perf_counter() - t_window >= args.seconds:
            break
    window_s = time.perf_counter() - t_window
    compiles = counter.count - compiles_before
    memory.between_window_steps()

    # correct: the first step against the reference (above), the losses,
    # no compilation, and where the state lives
    quarters = stats.quarter_means(losses)
    if not stats.loss_fell(losses, start=warm_losses[0]):
        problems.append(f"loss did not fall or is not finite: before any "
                        f"step {warm_losses[0]}, the window's first quarter "
                        f"{quarters[0]}, its last quarter {quarters[1]}")
    numbers["last_quarter_loss_under"] = [quarters[1], warm_losses[0]]
    numbers["compiles_in_window"] = [compiles, 0]
    if compiles:
        problems.append(f"{compiles} compilations inside the window")
    scope = fluid.global_scope()
    params = fluid.default_main_program().all_parameters()
    want = {d.id for d in devices}
    for p in params:
        on = {d.id for d in scope.find_var(p.name).devices()}
        if on != want:
            problems.append(f"parameter {p.name} on devices {sorted(on)}, "
                            f"expected {sorted(want)}")
            break
    apart = None
    if n > 1:
        for name, arr in batch.items():
            shapes = {tuple(s.data.shape) for s in arr.addressable_shards}
            if shapes != {(per_chip,) + tuple(arr.shape[1:])} or \
                    len(arr.addressable_shards) != n:
                problems.append(f"feed {name}: shards {shapes}, expected one "
                                f"[{per_chip}, ...] on each of {n} devices")
        whole, apart = copies_apart(scope.find_var(params[-1].name))
        if not whole:
            problems.append(f"parameter {params[-1].name} is not a whole "
                            "copy on each device")
        numbers["copies_apart"] = [apart, COPIES_ATOL]
        if whole and not apart <= COPIES_ATOL:
            problems.append(f"parameter {params[-1].name} differs between "
                            f"the chips after the window by {apart}, over "
                            f"{COPIES_ATOL}")

    obs = {
        "kind": "train", "chips": n, "window_s": window_s,
        "setup_s": setup_s, "steps": len(losses),
        "samples_per_step": batch_size,
        "compiles_in_window": compiles,
        "compiles_in_setup": compiles_before,
        "compile_seconds_in_setup": counter.seconds,
        "flops_per_sample": mod.flops_per_sample(cfg),
        "losses": [warm_losses[0], losses[0], losses[-1]],
        "loss_quarters": list(quarters),
        "window_losses": losses,
        "copies_apart": apart,
        "reference": found,
        "device_kind": devices[0].device_kind,
        "platform": devices[0].platform,
    }
    obs["end_to_end"] = {
        "train_samples_per_s": len(losses) * batch_size / window_s,
        "setup_s": setup_s,
    }
    if args.trace:
        logdir = os.path.join(args.trace_dir, cell.name)
        budget = float(cell.traffic["trace_seconds"])
        trace.start(logdir)
        t0 = time.perf_counter()
        traced_steps = 0
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                one_step()
                traced_steps += 1
                if time.perf_counter() - t0 >= budget:
                    break
        jax.profiler.stop_trace()
        obs["trace"] = trace.reduce(trace.load(logdir))
        obs["trace_steps"] = traced_steps
    obs["allocator_peak_bytes"] = _device.allocator_peak_bytes(devices)
    obs["program_temp_bytes"] = _device.largest_program_temp_bytes(devices)
    # the allocator does not see a running program's temporaries: what it
    # held when a step was launched plus that step's, at the fuller of the
    # two moments (the first step, a step of the window)
    obs["first_step_in_use_bytes"] = memory.first_in_use
    obs["window_step_in_use_bytes"] = memory.window_in_use
    obs["step_temp_bytes"] = memory.step_temp
    obs["memory_limit_bytes"] = _device.memory_limit_bytes(devices)
    obs["memory_peak_bytes"] = memory.peak()
    if not _device.fits(obs["memory_peak_bytes"], obs["memory_limit_bytes"]):
        print(f"[bench] WARNING: memory_peak_bytes "
              f"{obs['memory_peak_bytes']} is over what a chip holds, "
              f"{obs['memory_limit_bytes']}: hbm_peak_gb.train is left out",
              flush=True)
    return {"correct": not problems, "problems": problems,
            "compared": numbers, "attempted": len(losses), "failed": 0,
            "obs": obs}


# After the window every chip has to hold the same whole copy of a parameter:
# a gradient that was not reduced over the chips leaves them apart by about
# the learning rate a step in every element (Adam).  An all-reduce gives
# every chip the same bits, so 0 is what is read; the room is for a reduction
# whose order differs between the chips, which is rounding (1e-10 a step).
COPIES_ATOL = 1e-6


def copies_apart(arr) -> tuple:
    """(whether every device holds a whole copy of `arr`, the largest
    absolute difference between the first device's copy and another's)."""
    import numpy as np

    copies = [np.asarray(s.data) for s in arr.addressable_shards]
    if {c.shape for c in copies} != {tuple(arr.shape)}:
        return False, None
    return True, max((float(np.max(np.abs(copies[0] - c)))
                      for c in copies[1:]), default=0.0)
