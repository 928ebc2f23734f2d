"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the main path once on one TPU through the public entry points
(`import paddle_tpu as fluid`, `fluid.Executor(fluid.TPUPlace())`,
`paddle_tpu.models.*`, `paddle_tpu.serving.*`) with no FLAGS_*/BENCH_* set,
so what runs is what a user gets:

  train/transformer  Transformer-base (d_model 512, 8 heads, 6+6 layers,
                     vocab 32000, S=256, flash attention, fused qkv), bs=32,
                     Adam, a few steps
  train/resnet50     ResNet-50 at 224x224, bs=256, Momentum, a few steps
  serve              the paged decoder at real width (d_model 2048, 16 heads,
                     head_dim 128, d_inner 8192, vocab 32000; DEPTH CUT to 4
                     layers), a handful of requests through
                     ContinuousBatchingLoop.run against the dense oracle

    python chip_smoke.py              # one chip, the three phases
    python chip_smoke.py --chips 4    # ONLY the four-chip phase and what it
                                      # is compared with (dp=4 training,
                                      # tp=4 sharded decode)
    python chip_smoke.py --rehearse   # tiny sizes on whatever jax finds (the
                                      # CPU rehearsal); never prints "ok": true
                                      # and always exits non-zero off a TPU

The device is checked FIRST: without a TPU the script exits non-zero before
building anything.  Any phase that raises or whose check fails makes the exit
non-zero.  One process — the one that holds the chip; no child is started.
The last stdout line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np


@dataclasses.dataclass
class Sizes:
    """Real sizes (the default) and the tiny ones of --rehearse."""

    # train/transformer
    tf_vocab: int = 32000
    tf_seq: int = 256
    tf_layers: int = 6
    tf_d_model: int = 512
    tf_d_inner: int = 2048
    tf_bs: int = 32
    # train/resnet50
    rn_img: int = 224
    rn_classes: int = 1000
    rn_bs: int = 256
    train_steps: int = 6
    # serve
    sv_d_model: int = 2048
    sv_heads: int = 16
    sv_d_inner: int = 8192
    sv_vocab: int = 32000
    sv_layers: int = 4      # depth cut: the widths above are the real ones
    sv_pages: int = 256
    sv_prompts: tuple = (5, 12, 12, 23, 31)
    sv_max_new: int = 6


REHEARSAL = Sizes(
    tf_vocab=64, tf_seq=16, tf_layers=1, tf_d_model=32, tf_d_inner=64,
    tf_bs=8, rn_img=32, rn_classes=10, rn_bs=8, train_steps=5,
    sv_d_model=64, sv_heads=4, sv_d_inner=128, sv_vocab=96, sv_layers=2,
    sv_pages=32, sv_prompts=(3, 5, 5, 9), sv_max_new=4)

# per-step loss agreement between the one-chip Executor and the dp=4
# ParallelExecutor from the same seed: same math, other reduction order,
# bf16 compute on the chip
DP_LOSS_RTOL = 2e-2


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileCounter:
    """Counts executables built or loaded from the persistent cache, from
    jax's own monitoring events."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self._EVENTS:
            self.count += 1
            self.seconds += duration


def mem_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}: bytes_in_use={st.get('bytes_in_use', 'n/a')} "
                     f"peak_bytes_in_use(process so far)="
                     f"{st.get('peak_bytes_in_use', 'n/a')}")
    return "; ".join(parts)


# ---------------------------------------------------------------------------
# training phases


def build_transformer(sz: Sizes, seed: int):
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    spec = models.transformer(models.TransformerConfig(
        src_vocab_size=sz.tf_vocab, trg_vocab_size=sz.tf_vocab,
        max_length=sz.tf_seq, n_layer=sz.tf_layers, d_model=sz.tf_d_model,
        d_inner=sz.tf_d_inner, use_flash_attention=True, fuse_qkv=True))
    fluid.optimizer.AdamOptimizer(learning_rate=5e-4).minimize(spec.loss)
    return spec


def build_resnet50(sz: Sizes, seed: int):
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    spec = models.resnet_imagenet(
        depth=50, class_num=sz.rn_classes, img_shape=(3, sz.rn_img, sz.rn_img))
    fluid.optimizer.MomentumOptimizer(
        learning_rate=0.01, momentum=0.9).minimize(spec.loss)
    return spec


def train_steps(run, steps: int, counter: CompileCounter):
    """`run()` -> loss of one step.  Returns (losses, compiles in the first
    step, compiles after it, step seconds)."""
    losses, secs = [], []
    c0 = counter.count
    after_first = None
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(np.ravel(np.asarray(run()))[0]))
        secs.append(time.perf_counter() - t0)
        if after_first is None:
            after_first = counter.count
    return losses, after_first - c0, counter.count - after_first, secs


def check_losses(name: str, losses) -> None:
    check(bool(np.isfinite(losses).all()), f"{name}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall: first {losses[0]} last {losses[-1]}")


def phase_train(name, spec, bs, sz, place, tpu, counter, want_kernel):
    """A few steps of one model through fluid.Executor(place)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import flags

    t_phase = time.perf_counter()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    device = place.jax_device()
    batch = jax.device_put(spec.synthetic_batch(bs, seed=0), device)

    def run():
        return exe.run(feed=batch, fetch_list=[spec.loss])[0]

    losses, first, later, secs = train_steps(run, sz.train_steps, counter)
    say(f"[{name}] bs={bs} losses={[round(v, 4) for v in losses]}")
    say(f"[{name}] first step {secs[0]:.2f}s (compile included, {first} "
        f"executables built), later steps "
        f"{[round(s, 4) for s in secs[1:]]}s, executables built after the "
        f"first step: {later}")
    check_losses(name, losses)
    check(later == 0, f"{name}: {later} compilations after the first step")

    # parameters live where the place says
    scope = fluid.global_scope()
    params = [p.name for p in fluid.default_main_program().all_parameters()]
    on = {d for n in params for d in scope.find_var(n).devices()}
    say(f"[{name}] {len(params)} parameters on {sorted(str(d) for d in on)}")
    check(on == {device}, f"{name}: parameters on {on}, expected {device}")

    # the program the Executor compiled: its own jit (the cache entry run()
    # used) lowered for its own arguments, under the trace scope run() had
    # — AMP tier and conv layout resolve at trace time.  jax keeps the
    # executable of a called jit to itself; compiling the same module again
    # is answered by the persistent cache the first step wrote
    c0, t0 = counter.count, time.perf_counter()
    with flags.tpu_trace_scope(tpu):
        compiled, feed_vals, state_vals, rng = exe.capture_program(
            feed=batch, fetch_list=[spec.loss])
        args = jax.device_put((feed_vals, state_vals, rng), device)
        executable = compiled.fn.lower(*args).compile()
    n_kernels = executable.as_text().count("tpu_custom_call")
    mem = executable.memory_analysis()
    say(f"[{name}] step program: tpu_custom_call x{n_kernels}, "
        f"temp={mem.temp_size_in_bytes} args={mem.argument_size_in_bytes} "
        f"bytes (read back in {time.perf_counter() - t0:.1f}s, "
        f"{counter.count - c0} executables built or loaded)")
    if tpu and want_kernel:
        check(n_kernels > 0, f"{name}: no Pallas kernel in the step program "
                             "(the flash forward fell to the reference tier)")
    say(f"[{name}] memory {mem_line([device])}")
    say(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")
    return losses


# ---------------------------------------------------------------------------
# serving


def serve_config(sz: Sizes):
    from paddle_tpu import serving

    return serving.DecodeConfig(
        vocab_size=sz.sv_vocab, d_model=sz.sv_d_model, n_head=sz.sv_heads,
        n_layer=sz.sv_layers, d_inner=sz.sv_d_inner,
        max_length=max(sz.sv_prompts) + sz.sv_max_new + 1)


def serve_requests(sz: Sizes, seed: int):
    from paddle_tpu import serving

    rng = np.random.RandomState(seed)
    return [serving.DecodeRequest(
        prompt=[int(t) for t in rng.randint(1, sz.sv_vocab, size=n)],
        max_new_tokens=sz.sv_max_new) for n in sz.sv_prompts]


def check_generated(name, got, want_tokens) -> None:
    for g, want in zip(got, want_tokens):
        check(g.error is None, f"{name}: sequence {g.seq_id} errored: {g.error}")
        check(bool(np.isfinite(np.stack(g.logits)).all()),
              f"{name}: non-finite logits in sequence {g.seq_id}")
        check(list(g.tokens) == list(want),
              f"{name}: sequence {g.seq_id} tokens {g.tokens} != {want}")


def greedy_oracle(params, cfg, g):
    """What full_decode would generate for g's prompt, from ONE dense
    full_forward over prompt + generated tokens instead of one per token
    (each per-token forward has a new shape, and eager jax compiles every
    op again for it).  Attention is causal, so row i of that forward is the
    row full_decode sees after i+1 tokens: if every generated token is the
    argmax of its row, full_decode generates the same sequence.  Returns
    (tokens, logits rows)."""
    from paddle_tpu import serving

    fed = list(g.prompt) + list(g.tokens[:-1])
    rows = serving.full_forward(params, cfg, fed)[len(g.prompt) - 1:]
    return [int(r.argmax()) for r in rows], rows


def phase_serve(sz: Sizes, seed: int, tpu: bool, counter: CompileCounter):
    import jax
    from paddle_tpu import serving
    from paddle_tpu.kernels.paged_attention import fallback_count

    t_phase = time.perf_counter()
    name = "serve"
    cfg = serve_config(sz)
    say(f"[{name}] DecodeConfig d_model={cfg.d_model} n_head={cfg.n_head} "
        f"head_dim={cfg.head_dim} d_inner={cfg.d_inner} vocab={cfg.vocab_size}"
        f" — n_layer CUT to {cfg.n_layer}; weights fp32 "
        "(what init_decode_params makes), KV pool fp32, page_size 16")
    # the weights go to the device once; left as host arrays every eager
    # step would upload them again
    params = jax.device_put(serving.init_decode_params(cfg, seed=seed))
    pool = serving.KVCachePool(
        num_pages=sz.sv_pages, page_size=16, num_layers=cfg.n_layer,
        num_heads=cfg.n_head, head_dim=cfg.head_dim)
    loop = serving.ContinuousBatchingLoop(params, cfg, pool, max_batch=4)
    say(f"[{name}] paged attention tier resolved: {loop.paged_impl}")
    if tpu:
        check(loop.paged_impl == "pallas",
              f"{name}: paged tier is {loop.paged_impl}, expected pallas")
    reqs = serve_requests(sz, seed)
    c0 = counter.count
    # both sides under "highest": the chip's default bf16 passes for fp32
    # matmuls could flip an argmax between two correct implementations
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        got = loop.run(reqs)
        t_loop = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = [greedy_oracle(params, cfg, g) for g in got]
        t_ref = time.perf_counter() - t0
    n_tok = sum(len(g.tokens) for g in got)
    say(f"[{name}] {len(reqs)} requests (prompts {list(sz.sv_prompts)}, "
        f"{sz.sv_max_new} new each): {n_tok} tokens in {t_loop:.2f}s, "
        f"{loop.prefill_steps} prefill + {loop.decode_steps} decode steps "
        f"(compile included; {counter.count - c0} executables built in the "
        f"phase); full_forward oracle {t_ref:.2f}s")
    check_generated(name, got, [w[0] for w in want])
    diff = max(float(np.max(np.abs(np.stack(g.logits) - w[1])))
               for g, w in zip(got, want))
    say(f"[{name}] greedy tokens equal full_decode's (argmax of the dense "
        f"full_forward at every generated position) for all {len(reqs)} "
        "requests, both under matmul precision 'highest'; all logits "
        f"finite; max |logit - oracle| {diff:.3e}")
    say(f"[{name}] paged fallback_count={fallback_count()}")
    check(fallback_count() == 0, f"{name}: paged attention fell back")
    say(f"[{name}] memory {mem_line(pool.k_pages.devices())}")
    say(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


# ---------------------------------------------------------------------------
# four chips: dp=4 training and tp=4 sharded decode, each against one chip


def check_one_shard_each(name, array, devices, shard_shape) -> None:
    shards = array.addressable_shards
    on = [s.device for s in shards]
    check(sorted(d.id for d in on) == sorted(d.id for d in devices),
          f"{name}: shards on {on}, expected one on each of {devices}")
    for s in shards:
        check(tuple(s.data.shape) == tuple(shard_shape),
              f"{name}: shard on {s.device} has shape {s.data.shape}, "
              f"expected {shard_shape}")


def phase_dp4(sz: Sizes, seed: int, place, tpu: bool, counter, devices):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    name = "chips4/train-dp4"
    t_phase = time.perf_counter()
    n = len(devices)
    spec = build_transformer(sz, seed)
    one = phase_train("chips4/train-one-chip", spec, sz.tf_bs, sz, place, tpu,
                      counter, want_kernel=True)

    spec = build_transformer(sz, seed)
    fluid.Executor(place).run(fluid.default_startup_program())
    mesh = make_mesh({"dp": n}, devices=devices)
    pe = ParallelExecutor(loss_name=spec.loss.name, mesh=mesh)
    batch = jax.device_put(spec.synthetic_batch(sz.tf_bs, seed=0),
                           mesh.batch_sharding())
    for k, v in batch.items():
        check_one_shard_each(f"{name}: feed {k}", v, devices,
                             (sz.tf_bs // n,) + v.shape[1:])
    say(f"[{name}] every feed holds a [{sz.tf_bs // n}, ...] shard on each "
        f"of {n} devices")

    def run():
        return pe.run(feed=batch, fetch_list=[spec.loss])[0]

    losses, first, later, secs = train_steps(run, sz.train_steps, counter)
    say(f"[{name}] global bs={sz.tf_bs} losses="
        f"{[round(v, 4) for v in losses]}")
    say(f"[{name}] first step {secs[0]:.2f}s (compile included), later steps "
        f"{[round(s, 4) for s in secs[1:]]}s, executables built after the "
        f"first step: {later}")
    check_losses(name, losses)
    check(later == 0, f"{name}: {later} compilations after the first step")
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, one)]
    say(f"[{name}] per-step |dp4 - one chip| / one chip = "
        f"{[round(r, 5) for r in rel]} (tolerance {DP_LOSS_RTOL})")
    check(max(rel) <= DP_LOSS_RTOL,
          f"{name}: loss differs from the one-chip run by {max(rel)}")
    # the trained state is replicated: a whole copy on each device
    p0 = fluid.default_main_program().all_parameters()[0]
    val = fluid.global_scope().find_var(p0.name)
    check_one_shard_each(f"{name}: parameter {p0.name}", val, devices,
                         val.shape)
    say(f"[{name}] parameter {p0.name} {tuple(val.shape)} has a copy on "
        f"each of {n} devices")
    say(f"[{name}] memory {mem_line(devices)}")
    say(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


def phase_tp4(sz: Sizes, seed: int, tpu: bool, devices):
    import jax
    from paddle_tpu import serving
    from paddle_tpu.kernels.paged_attention import fallback_count
    from paddle_tpu.serving.distributed import ShardedDecodeProgram

    name = "chips4/serve-tp4"
    t_phase = time.perf_counter()
    n = len(devices)
    cfg = serve_config(sz)
    say(f"[{name}] DecodeConfig d_model={cfg.d_model} n_head={cfg.n_head} "
        f"head_dim={cfg.head_dim} d_inner={cfg.d_inner} vocab={cfg.vocab_size}"
        f" — n_layer CUT to {cfg.n_layer}")
    host_params = serving.init_decode_params(cfg, seed=seed)
    reqs = serve_requests(sz, seed)

    with jax.default_matmul_precision("highest"):
        pool1 = serving.KVCachePool(
            num_pages=sz.sv_pages, page_size=16, num_layers=cfg.n_layer,
            num_heads=cfg.n_head, head_dim=cfg.head_dim)
        one = serving.ContinuousBatchingLoop(
            jax.device_put(host_params, devices[0]), cfg, pool1, max_batch=4)
        t0 = time.perf_counter()
        want = one.run(serve_requests(sz, seed))
        say(f"[{name}] single-device loop ({one.paged_impl}): "
            f"{time.perf_counter() - t0:.2f}s")

        prog = ShardedDecodeProgram(host_params, cfg, devices=devices)
        pool = prog.make_pool(num_pages=sz.sv_pages, page_size=16)
        loop = serving.ContinuousBatchingLoop(None, None, pool, max_batch=4,
                                              program=prog)
        t0 = time.perf_counter()
        got = loop.run(reqs)
        say(f"[{name}] tp={n} loop ({prog.paged_impl}): "
            f"{time.perf_counter() - t0:.2f}s, {loop.prefill_steps} prefill "
            f"+ {loop.decode_steps} decode steps (compile included)")
    if tpu:
        check(prog.paged_impl == "pallas" and one.paged_impl == "pallas",
              f"{name}: paged tiers {one.paged_impl}/{prog.paged_impl}")
    check_generated(name, got, [w.tokens for w in want])
    diff = max(float(np.max(np.abs(np.stack(g.logits) - np.stack(w.logits))))
               for g, w in zip(got, want))
    say(f"[{name}] tokens equal the single-device loop for all {len(reqs)} "
        f"requests (both under 'highest'); max |logit difference| {diff:.3e}")

    L, H, P, ps, D = pool.k_pages.shape
    for nm, arr in (("k_pages", pool.k_pages), ("v_pages", pool.v_pages)):
        check_one_shard_each(f"{name}: {nm}", arr, devices,
                             (L, H // n, P, ps, D))
    wq = prog.params["layers"][0]["wq"]
    check_one_shard_each(f"{name}: wq", wq, devices,
                         (wq.shape[0], wq.shape[1] // n))
    say(f"[{name}] KV pool {tuple(pool.k_pages.shape)}: each of {n} devices "
        f"holds its [{L}, {H // n}, {P}, {ps}, {D}] shard; wq sharded "
        f"[{wq.shape[0]}, {wq.shape[1] // n}] per device")
    say(f"[{name}] paged fallback_count={fallback_count()}")
    check(fallback_count() == 0, f"{name}: paged attention fell back")
    say(f"[{name}] memory {mem_line(devices)}")
    say(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip phase and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever jax finds; never prints "
                         '"ok": true and exits non-zero off a TPU')
    args = ap.parse_args()

    t_start = time.perf_counter()
    import jax

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    tpu = dev0.platform == "tpu"
    if not tpu and not args.rehearse:
        sys.stderr.write(
            f"chip_smoke: jax found no TPU ({device}); nothing was built\n")
        return 2
    say(f"[device] {device}")
    if device["count"] < args.chips:
        sys.stderr.write(
            f"chip_smoke: --chips {args.chips} but jax found {device}\n")
        return 2
    devices = jax.devices()[:args.chips]
    device["count"] = args.chips

    # the package comes after the device check: nothing is built without one
    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.core import amp
    from paddle_tpu.core.compiler import default_compile_cache

    say(f"[cache] compile cache directory: {default_compile_cache()}")
    sz = REHEARSAL if args.rehearse else Sizes()
    place = fluid.TPUPlace() if tpu else fluid.CPUPlace()
    with flags.tpu_trace_scope(tpu):  # the Executor's own rule
        say(f"[defaults] place={place!r}: AMP dtype={amp.amp_dtype()} "
            f"keep_output={amp.keep_output()}, conv layout="
            f"{flags.conv_layout()} (the trace scope's own choice, no flag "
            "set)")
    counter = CompileCounter()

    if args.chips == 4:
        phase_dp4(sz, args.seed, place, tpu, counter, devices)
        phase_tp4(sz, args.seed, tpu, devices)
    else:
        phase_train("train/transformer", build_transformer(sz, args.seed),
                    sz.tf_bs, sz, place, tpu, counter, want_kernel=True)
        phase_train("train/resnet50", build_resnet50(sz, args.seed),
                    sz.rn_bs, sz, place, tpu, counter, want_kernel=False)
        phase_serve(sz, args.seed, tpu, counter)

    say(f"[total] {time.perf_counter() - t_start:.1f}s, {counter.count} "
        f"executables built or loaded ({counter.seconds:.1f}s)")
    if args.rehearse:
        # a rehearsal proves paths, not the chip: never an "ok": true line
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
