"""Round benchmark: ResNet-50 train images/sec AND Transformer train
tokens/sec on the available chip, in one run.

Prints ONE JSON line.  Top-level metric/value/unit/vs_baseline are the
ResNet-50 numbers (vs the reference's best published ResNet-50 training
throughput: 81.69 img/s, MKL-DNN on 2x Xeon 6148 —
benchmark/IntelOptimizedPaddle.md:43-47; the reference publishes no
GPU/fluid-era ResNet-50 number, see BASELINE.md).  "extra_metrics" carries
the Transformer tokens/sec and per-model MFU estimates from analytic FLOPs.

The step loop is fully pipelined: feeds are numpy, the Executor device_puts
them asynchronously, fetches stay on device (return_numpy=False) so nothing
blocks until the final block_until_ready — the reference gets the same
overlap from its double-buffer reader ops
(operators/reader/create_double_buffer_reader_op.cc).

Env knobs: BENCH_BS (resnet bs, default 256), BENCH_TRANSFORMER_BS (default
16), BENCH_STEPS (default 20), BENCH_MODELS (comma list, default
"resnet50,transformer"), BENCH_AMP ("1": bf16 matmul/conv compute; "keep" =
bf16 activations between matmuls; "0" = fp32; unset: the policy stays
unset, which a TPU program resolves to "keep"), BENCH_FLASH (default "1"),
BENCH_LAYOUT ("NCHW"/"NHWC" conv internal layout; unset: "auto", NHWC for a
TPU program; the chip's peak for MFU comes from DEVICE_PEAKS by
device_kind),
BENCH_DATA=pyreader (feed through the py_reader worker-thread pipeline
instead of pre-staged device arrays — proves the data stack keeps up).

BENCH_LOWER_ONLY=1: per-model chip-less TPU lowering gate (no timed
run).  BENCH_COST_ONLY=1: per-model bytes/step table from the TPU
compiler's own cost model via a chip-less AOT topology compile
(BENCH_COST_PLATFORM=native for the host executable instead).  Both run
on a CPU host and mark their rows "chipless".

The device: TPUPlace(), which raises where jax finds no TPU — there is no
fallback.  Only JAX_PLATFORMS=cpu makes it CPUPlace(): a declared CPU run
(the tier-1 tests).  Every result row carries "platform", "device_kind"
and "device_count", so a CPU row cannot pass for a chip number.

FLAGS_observability=1: the unified telemetry spine records the run —
per-step executor metrics (wall-time histogram, compile-cache hit/miss),
trace spans, and the StepStats p50/p99 ring buffer — and bench writes the
artifacts into BENCH_OBS_DIR (default "obs_run"): metrics.prom
(Prometheus text), metrics.json, trace.json (Perfetto-loadable, named
threads), report.json (step-time summary + regression verdicts).  Render
with `python tools/obsdump.py <dir>`.  BENCH_BASELINE=<path to a previous
bench artifact or {metric: value} JSON> gates every measured model
against its banked number and attaches pass/fail verdicts with deltas to
the output ("regression"); BENCH_BASELINE_TOL (default 0.05) is the
relative tolerance.  FLAGS_observability_cost=native|tpu additionally
records each compiled program's bytes/step (the chip-free A/B loop).

BENCH_CKPT_DIR=<dir>: opt-in resumable runs — before the timed region the
model restores from the newest valid checkpoint under <dir>/<model>/
(resilience.CheckpointManager, corrupt checkpoints skipped), every
BENCH_CKPT_EVERY steps (default 50) an ASYNC verified checkpoint drains
in the background, and a final synchronous one lands after the timed
region, so a long run killed mid-way (preemption, deadline) resumes
instead of restarting.  BENCH_CKPT_KEEP (default 2) bounds rotation.
Checkpoint cadence rides inside the timed region (async write threads
share the host), so resumable numbers carry "ckpt_every" in their result
for attribution; leave BENCH_CKPT_DIR unset for clean measurements.

On failure the output is STILL one parseable JSON line ({"metric":
"error", ...}, with "partial_results" where some models finished), and the
exit code is non-zero — as it is whenever any requested model failed.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REF_RESNET50_IMG_S = 81.69  # IntelOptimizedPaddle.md:43-47 (bs=64, MKL-DNN)

# training FLOPs ~= 3x forward (fwd + 2x bwd)
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9  # 224x224, standard count


def _transformer_train_flops_per_token(cfg) -> float:
    d, di, L, S = cfg.d_model, cfg.d_inner, cfg.n_layer, cfg.max_length
    matmul_params = (
        L * (4 * d * d + 2 * d * di)        # encoder: self-attn + ffn
        + L * (8 * d * d + 2 * d * di)      # decoder: self+cross attn + ffn
        + d * cfg.trg_vocab_size            # output projection
    )
    # attention score/value matmuls: ~4*S*d fwd per token per attn block,
    # 3 blocks per (enc,dec) layer pair; x3 for training
    attn = 3 * 4 * S * d * 3 * L
    return 6 * matmul_params + attn


# published per-chip peaks keyed by jax's device_kind.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
# A chip that is not here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def _bench_place():
    """TPUPlace(), which raises where jax finds no TPU — unless
    JAX_PLATFORMS says cpu outright, a declared CPU run."""
    import paddle_tpu as fluid

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return fluid.CPUPlace()
    return fluid.TPUPlace()


def _device_row(device) -> dict:
    import jax

    return {"platform": device.platform, "device_kind": device.device_kind,
            "device_count": len(jax.devices())}


def _peak_flops(device):
    """bf16 peak FLOP/s MFU is taken against; None on a declared CPU run,
    which reports no MFU."""
    if device.platform == "cpu":
        return None
    if device.device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"no published peak for device_kind {device.device_kind!r} in "
            "bench.DEVICE_PEAKS: add it with its source to report MFU")
    return DEVICE_PEAKS[device.device_kind]["bf16_flops"]


def _apply_config(amp, layout) -> None:
    """BENCH_AMP / BENCH_LAYOUT where given; otherwise the AMP policy stays
    unset and the layout "auto", which flags.tpu_trace_scope resolves to
    what the chip measured best (keep-tier bf16, NHWC), as the benchmark's
    cells rely on."""
    import paddle_tpu as fluid
    from paddle_tpu.core import amp as amp_policy

    if amp is None:
        amp_policy.reset_amp()
    elif amp == "0":
        fluid.disable_amp()
    else:
        fluid.enable_amp("bfloat16", keep_output=(amp == "keep"))
    fluid.set_flags({"FLAGS_conv_layout": layout or "auto"})


def run_model(model: str, steps: int, peak_flops: float,
              amp=None, layout=None) -> dict:
    """One model's timed steady-state loop.  For a device profile of a
    cell use `python3 benchmark/run.py --workload <cell> --trace 1` and
    open bench_out/trace/<cell> in TensorBoard or Perfetto: the
    executor.* spans sit above the device rows."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    _apply_config(amp, layout)

    if model == "resnet50":
        # r2 on-chip sweep: bs=256 gave 1715.6 img/s vs 1674.7 at bs=128
        bs = int(os.environ.get("BENCH_BS", "256"))
        spec = models.resnet_imagenet(depth=50, class_num=1000)
        unit = "images/sec"
        items_per_step = bs
        metric = "resnet50_train_images_per_sec_per_chip"
        baseline = REF_RESNET50_IMG_S
        flops_per_item = RESNET50_TRAIN_FLOPS_PER_IMG
        lr = 0.1
    elif model in ("transformer", "transformer_longctx"):
        # r3 on-chip sweep: bs=32 115.3k tok/s vs bs=16 106.9k, bs=64 flat.
        # _longctx: S=2048 (BENCH_LONGCTX_S), bs=2 — the first real
        # long-sequence datapoint for the flash/blockwise stack beyond the
        # S=16 structural toys (VERDICT r3 item 8); flash fwd keeps HBM
        # O(S*D) instead of the [B,H,S,S] probability matrix
        longctx = model == "transformer_longctx"
        if longctx:
            bs = int(os.environ.get("BENCH_LONGCTX_BS", "2"))
            seq = int(os.environ.get("BENCH_LONGCTX_S", "2048"))
        else:
            bs = int(os.environ.get("BENCH_TRANSFORMER_BS", "32"))
            seq = 256
        cfg = models.TransformerConfig(
            src_vocab_size=32000, trg_vocab_size=32000, max_length=seq,
            use_flash_attention=os.environ.get("BENCH_FLASH", "1") != "0",
            fuse_qkv=os.environ.get("BENCH_FUSE_QKV", "1") != "0",
            use_recompute=longctx,  # layer remat: the long-S memory policy
        )
        spec = models.transformer(cfg)
        unit = "tokens/sec"
        items_per_step = bs * cfg.max_length
        metric = (model + "_train_tokens_per_sec_per_chip")
        baseline = None  # no reference number exists (BASELINE.md)
        flops_per_item = _transformer_train_flops_per_token(cfg)
        lr = 1e-4
    elif model == "deepfm":
        bs = int(os.environ.get("BENCH_DEEPFM_BS", "512"))
        vocab = int(os.environ.get("BENCH_DEEPFM_VOCAB", "1000000"))
        spec = models.deepfm(num_fields=26, vocab_size=vocab, embed_dim=10)
        unit = "examples/sec"
        items_per_step = bs
        metric = "deepfm_ctr_train_examples_per_sec_per_chip"
        baseline = None  # no reference number exists (BASELINE.md)
        # dominated by the DNN matmuls: fwd ~2*sum(in*out) per example
        dnn_flops = 2 * (26 * 10 * 400 + 400 * 400 * 2 + 400)
        flops_per_item = 3 * dnn_flops
        lr = 1e-3
    elif model == "lstm":
        # BASELINE.md "LSTM text-cls (2xlstm+fc)" IMDB config: bs=64,
        # h=512, seq len 100 (benchmark/README.md:112-127; the published
        # table mixes units, so no vs_baseline ratio is claimed)
        bs = int(os.environ.get("BENCH_LSTM_BS", "64"))
        spec = models.stacked_dynamic_lstm(lstm_size=512, stacked_layers=2)
        unit = "examples/sec"
        items_per_step = bs
        metric = "lstm_textcls_train_examples_per_sec_per_chip"
        baseline = None
        # per token per layer: fc projection (h->4h) AND recurrent matmul
        # (h->4h); 2 layers + the input fc; x3 for training.  Token count
        # is measured from the staged batches below (sequence lengths are
        # drawn per example), not assumed = max_len.
        flops_per_item = None  # filled in after batches are staged
        lr = 0.01
    elif model == "se_resnext":
        # benchmark/fluid se_resnext config (SE-ResNeXt-50 32x4d); the
        # reference publishes no absolute number for it (BASELINE.md)
        bs = int(os.environ.get("BENCH_SE_RESNEXT_BS", "128"))
        spec = models.se_resnext()
        unit = "images/sec"
        items_per_step = bs
        metric = "se_resnext50_train_images_per_sec_per_chip"
        baseline = None
        flops_per_item = 3 * 4.3e9  # fwd ~4.3 GFLOP @224 (SE adds ~5%)
        lr = 0.1
    elif model == "machine_translation":
        # benchmark/fluid machine_translation config: attention seq2seq
        # over ragged LoD batches (dynamic_gru encoder, per-step attention)
        bs = int(os.environ.get("BENCH_MT_BS", "64"))
        spec = models.machine_translation()
        unit = "examples/sec"
        items_per_step = bs
        metric = "machine_translation_train_examples_per_sec_per_chip"
        baseline = None
        flops_per_item = None  # follows the real token count, like lstm
        lr = 0.01
    elif model == "lenet":
        bs = int(os.environ.get("BENCH_BS", "64"))
        spec = models.lenet5()
        unit = "images/sec"
        items_per_step = bs
        metric = "mnist_train_images_per_sec_per_chip"
        baseline = None
        flops_per_item = 3 * 5e6
        lr = 0.01
    elif model == "alexnet":
        # IntelOptimizedPaddle.md:61-66: train bs=64 399.00 img/s (MKL-DNN)
        bs = int(os.environ.get("BENCH_ALEXNET_BS", "64"))
        spec = models.alexnet()
        unit = "images/sec"
        items_per_step = bs
        metric = "alexnet_train_images_per_sec_per_chip"
        baseline = 399.00
        flops_per_item = 3 * 1.4e9  # fwd ~0.7 GMAC @227
        lr = 0.01
    elif model == "googlenet":
        # IntelOptimizedPaddle.md:52-56: train bs=64 250.46 img/s (MKL-DNN)
        bs = int(os.environ.get("BENCH_GOOGLENET_BS", "64"))
        spec = models.googlenet()
        unit = "images/sec"
        items_per_step = bs
        metric = "googlenet_train_images_per_sec_per_chip"
        baseline = 250.46
        flops_per_item = 3 * 3.0e9  # fwd ~1.5 GMAC @224
        lr = 0.01
    elif model in ("vgg19", "vgg19_infer", "vgg19_infer_int8"):
        # IntelOptimizedPaddle.md:33-38/74-79: train bs=64 28.46 img/s,
        # infer bs=1 75.07 img/s (MKL-DNN, 2x Xeon 6148, ImageNet shapes).
        # _int8: same infer config through QuantizeTranspiler.freeze_program
        # (mul_int8/conv2d_int8 ops — the MXU's int8 path).
        infer = "_infer" in model
        bs = int(os.environ.get(
            "BENCH_VGG_INFER_BS" if infer else "BENCH_VGG_BS",
            "1" if infer else "64"))
        spec = models.vgg19()
        unit = "images/sec"
        items_per_step = bs
        metric = (model + "_images_per_sec_per_chip" if infer
                  else "vgg19_train_images_per_sec_per_chip")
        baseline = 75.07 if infer else 28.46
        flops_per_item = 19.6e9 if infer else 3 * 19.6e9
        lr = 0.01
    else:
        raise SystemExit(f"unknown BENCH_MODELS entry {model!r} "
                         "(expected resnet50|transformer|transformer_longctx|"
                         "deepfm|lstm|lenet|alexnet|googlenet|vgg19|"
                         "vgg19_infer|vgg19_infer_int8|se_resnext|"
                         "machine_translation)")

    run_program = None
    fetch_var = spec.loss
    if model == "deepfm":
        # lazy sparse adam over the 1e6-row tables: only touched rows
        # update, so the step never sweeps the vocab (the SelectedRows path)
        fluid.optimizer.AdamOptimizer(
            learning_rate=lr, lazy_mode=True
        ).minimize(spec.loss)
    elif "_infer" in model:
        # inference: no optimizer; dropout/batch_norm switch to test mode
        # (the predictor API wraps this same clone, inference/__init__.py)
        if model.endswith("_int8"):
            from paddle_tpu.contrib.quantize import QuantizeTranspiler

            qt = QuantizeTranspiler()
            qt.training_transpile()
        run_program = fluid.default_main_program().clone(for_test=True)
        fetch_var = spec.extras["predict"]
    else:
        fluid.optimizer.MomentumOptimizer(
            learning_rate=lr, momentum=0.9
        ).minimize(spec.loss)

    place = _bench_place()
    device_row = _device_row(place.jax_device())
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    if model.endswith("_int8"):
        # weights are in scope now; quantize them offline and rewrite the
        # inference clone to the int8 ops
        qt.freeze_program(run_program)

    batches_np = [spec.synthetic_batch(bs, seed=i) for i in range(4)]

    if os.environ.get("BENCH_LOWER_ONLY", "0") == "1":
        # chip-less gate: TPU-lower the exact step this config would
        # time (chip trace scope forced) on the CPU host — catches
        # chip-only Mosaic/pallas failures without spending chip time.
        # Hoisted ABOVE device staging and pyreader startup (it only
        # needs exe/run_program/batches_np[0]/fetch_var): the gate must
        # not return with a reader thread still running.
        nbytes = exe.tpu_lowering_check(
            program=run_program, feed=batches_np[0],
            fetch_list=[fetch_var])
        return {"metric": f"{model}_tpu_lowering", "value": 1,
                "unit": "ok", "vs_baseline": None,
                "module_bytes": nbytes, "chipless": True, **device_row}

    if os.environ.get("BENCH_COST_ONLY", "0") == "1":
        # chip-less bytes/step table: the TPU compiler's own cost model
        # via an AOT topology compile (core/aot_tpu.py) — per-model HBM
        # traffic without a chip.  BENCH_COST_PLATFORM=native
        # analyzes the host-compiled executable instead.
        plat = os.environ.get("BENCH_COST_PLATFORM", "tpu")
        ca = exe.cost_analysis(
            program=run_program, feed=batches_np[0],
            fetch_list=[fetch_var],
            platform=None if plat in ("", "native") else plat)
        return {"metric": f"{model}_bytes_per_step",
                "value": ca.get("bytes accessed"), "unit": "bytes",
                "vs_baseline": None,
                "cost_flops_per_step": ca.get("flops"),
                "cost_platform": plat, "chipless": True, **device_row}

    from paddle_tpu.core.lod import LoDValue

    data_mode = os.environ.get("BENCH_DATA", "staged")
    use_pyreader = (
        data_mode == "pyreader" and run_program is None
        and not any(isinstance(v, LoDValue) for v in batches_np[0].values())
    )
    if data_mode == "pyreader" and not use_pyreader:
        sys.stderr.write(
            f"# {model}: BENCH_DATA=pyreader unsupported here (inference "
            "program or LoD batches) — falling back to staged arrays\n")
    reader = None
    if use_pyreader:
        # feed through the real input pipeline: a worker thread pushes
        # numpy batches into the bounded queue, exe.run(feed=None) pops
        # and device_puts asynchronously (reference analogue: py_reader +
        # create_double_buffer_reader_op.cc) — proves the data stack can
        # keep the chip fed, not just pre-staged arrays
        from paddle_tpu.layers.io_pyreader import PyReader

        names = sorted(batches_np[0])
        reader = PyReader(
            names,
            [list(np.shape(batches_np[0][n])) for n in names],
            [np.asarray(batches_np[0][n]).dtype.name for n in names],
            [0] * len(names),
            capacity=8,
        )

        def provider():
            i = 0
            while True:
                b = batches_np[i % len(batches_np)]
                yield [b[n] for n in names]
                i += 1

        reader.decorate_tensor_provider(provider)
        prog = fluid.default_main_program()
        prog._py_readers = [reader]
        reader.start()
        batches = batches_np  # only len() is used below in pyreader mode
    else:
        # stage the synthetic batches on device ONCE: this mode measures
        # the training step, not the host->chip link (BENCH_DATA=pyreader
        # measures the pipelined path)
        dev = place.jax_device()
        batches = [jax.device_put(b, dev) for b in batches_np]
        jax.block_until_ready(batches)

    if flops_per_item is None:  # ragged models: flops follow REAL tokens
        from paddle_tpu.core.lod import LoDValue

        tokens = [
            float(np.sum(np.asarray(v.lengths)))
            for b in batches for v in b.values() if isinstance(v, LoDValue)
        ]
        avg_tokens = (sum(tokens) / len(batches)) / bs if tokens else 100.0
        if model == "machine_translation":
            # three LoD streams (src/trg/lbl) were summed: per-stream avg
            avg_pairs = avg_tokens / 3.0
            # fwd/token-pair: encoder (in-fc 512->1536, bigru 2x3x512^2,
            # proj 1024->512) ~5.7 MFLOP + decoder (out-proj 512->10000
            # dominates, gru+attention) ~12 MFLOP; x3 for training
            flops_per_item = 3 * avg_pairs * (5.7e6 + 12.0e6)
        else:  # stacked lstm
            flops_per_item = (
                3 * avg_tokens * (2 * 2 * 16 * 512 * 512 + 2 * 512 * 512)
            )

    # opt-in resumable runs: restore params from the newest valid
    # checkpoint, then drain async verified checkpoints on a cadence so a
    # killed long run (preemption, driver deadline) resumes from
    # its last checkpoint instead of from scratch
    ckpt_mgr = None
    ckpt_every = 0
    ckpt_pending = [None]  # the one in-flight async save handle

    def _ckpt_save(step_no, asynchronous):
        # at most ONE async writer in flight: joining the previous save
        # first bounds memory (each writer holds a host param snapshot)
        # and is natural backpressure when the disk is slower than the
        # cadence; a failed background write is WARNED, not swallowed —
        # and never kills the timed run.  The join and the new save are
        # independent failures: a transient error in the PREVIOUS write
        # must not abort THIS save (the disk may have recovered)
        if ckpt_pending[0] is not None:
            try:
                ckpt_pending[0].wait()
            except Exception as e:
                sys.stderr.write(
                    f"# {model}: async checkpoint write FAILED "
                    f"({type(e).__name__}: {e}) — run continues, resume "
                    "point unchanged\n")
            ckpt_pending[0] = None
        try:
            ckpt_pending[0] = ckpt_mgr.save(
                step_no, asynchronous=asynchronous)
        except Exception as e:
            sys.stderr.write(
                f"# {model}: checkpoint at step {step_no} FAILED "
                f"({type(e).__name__}: {e}) — run continues, resume "
                "point unchanged\n")

    ckpt_base = 0
    if os.environ.get("BENCH_CKPT_DIR") and run_program is None:
        from paddle_tpu.resilience import CheckpointManager

        ckpt_mgr = CheckpointManager(
            os.path.join(os.environ["BENCH_CKPT_DIR"], model),
            keep_last=int(os.environ.get("BENCH_CKPT_KEEP", "2")),
        )
        ckpt_every = int(os.environ.get("BENCH_CKPT_EVERY", "50"))
        restored = ckpt_mgr.restore_or_init()
        if restored is not None:
            # resumed runs keep numbering PAST the restored step: saving
            # from 0 again would sit below the newest valid checkpoint
            # and be GC'd on arrival (and LATEST would go stale)
            ckpt_base = restored.step
            sys.stderr.write(
                f"# {model}: resumed params from checkpoint "
                f"step_{restored.step}\n")

    # warmup: one pass over EVERY staged batch (variable-length batches
    # each have their own XLA shape) plus one extra step so the
    # committed-state jit variant also compiles before timing starts
    def step_feed(i):
        return None if use_pyreader else batches[i % len(batches)]

    warm = None
    for i in range(len(batches) + 1):
        (warm,) = exe.run(program=run_program, feed=step_feed(i),
                          fetch_list=[fetch_var], return_numpy=False)
    jax.block_until_ready(warm)

    t0 = time.perf_counter()
    loss_v = None
    for i in range(steps):
        (loss_v,) = exe.run(program=run_program, feed=step_feed(i),
                            fetch_list=[fetch_var], return_numpy=False)
        if ckpt_mgr and ckpt_every and (i + 1) % ckpt_every == 0:
            # async: snapshot now, write in the background
            _ckpt_save(ckpt_base + i + 1, asynchronous=True)
    jax.block_until_ready(loss_v)
    dt = time.perf_counter() - t0
    if ckpt_mgr:
        # final synchronous checkpoint outside the timed region: the run
        # is resumable from its end state (joins the in-flight async
        # writer first, surfacing any background write failure)
        _ckpt_save(ckpt_base + steps, asynchronous=False)
    if reader is not None:
        reader.reset()

    value = items_per_step * steps / dt
    if peak_flops and model.endswith("_int8"):
        # the frozen graph runs on the int8 MXU path, whose peak is ~2x
        # the bf16 peak
        peak_flops = peak_flops * 2
    # no peak (a declared CPU run) = no MFU
    mfu = value * flops_per_item / peak_flops if peak_flops else None
    tag = "final_fetch" if "_infer" in model else "final_loss"
    sys.stderr.write(
        f"# {model}: {device_row['platform']} bs={bs} steps={steps} "
        f"wall={dt:.2f}s mfu={mfu if mfu is None else round(mfu, 3)} "
        f"{tag}={float(np.ravel(np.asarray(loss_v))[0]):.4f}\n"
    )
    result = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / baseline, 3) if baseline else None,
        "mfu": None if mfu is None else round(mfu, 4),
        **device_row,
        # which input path actually ran (pyreader silently falls back for
        # inference programs / LoD batches)
        "data": "pyreader" if use_pyreader else "staged",
    }
    if ckpt_mgr:
        # attribution: async checkpoint writers shared the host with the
        # timed region, so resumable numbers are labeled as such
        result["ckpt_every"] = ckpt_every
    if os.environ.get("BENCH_COST", "0") == "1" and not use_pyreader:
        # XLA cost accounting of the exact compiled step: bytes/step is
        # the number that validates (or corrects) paper HBM-traffic
        # floors like the 65 GB ResNet-50 estimate.  Opt-in: the
        # trace/lower/compile re-walk is only cheap when the persistent
        # compile cache is on
        try:
            ca = exe.cost_analysis(program=run_program, feed=step_feed(0),
                                   fetch_list=[fetch_var])
            result["bytes_per_step"] = ca.get("bytes accessed")
            result["cost_flops_per_step"] = ca.get("flops")
        except Exception as e:  # never lose the timed number to accounting
            result["cost_analysis_error"] = str(e)[:200]
    # feature provenance, so a number is attributable to the config that
    # produced it (fused smoothed CE, recompute)
    feats = {}
    if model in ("transformer", "transformer_longctx"):
        feats["fuse_smooth_ce"] = cfg.fuse_smooth_ce
        feats["recompute"] = cfg.use_recompute
    if feats:
        result["features"] = feats
    return result


def _attach_observability(primary: dict, results: list) -> dict:
    """BENCH_BASELINE regression verdicts + (FLAGS_observability)
    telemetry artifacts.  Never fails the bench: every path — including
    a malformed BENCH_BASELINE_TOL — degrades to an *_error field in
    the artifact."""
    try:
        from paddle_tpu import observability as obs
    except Exception:
        return primary
    baseline = os.environ.get("BENCH_BASELINE")
    try:
        tol = float(os.environ.get("BENCH_BASELINE_TOL", "0.05"))
    except ValueError as e:
        # keep gating with the default tolerance: a typo'd knob must not
        # silently disable the regression gate CI relies on
        primary["regression_error"] = (
            f"BENCH_BASELINE_TOL: {e}; gated with default 0.05")[:200]
        tol = 0.05
    report = None
    if obs.enabled():
        obs_dir = os.environ.get("BENCH_OBS_DIR", "obs_run")
        try:
            report = obs.export_run(
                obs_dir, results=results,
                baseline_path=baseline or None, tolerance=tol)
            st = report.get("step_time", {})
            primary["observability"] = {
                "dir": obs_dir,
                "steps_recorded": st.get("count", 0),
                "step_time_p50_s": st.get("p50_s"),
                "step_time_p99_s": st.get("p99_s"),
            }
        except Exception as e:  # noqa: BLE001 — telemetry must not
            # lose the timed numbers
            primary["observability_error"] = str(e)[:200]
    if baseline:
        # gate ONCE: reuse the verdicts export_run just banked in
        # report.json; compute directly only when no report was written
        if report is not None and "regression" in report:
            primary["regression"] = report["regression"] or [
                {"verdict": "no_baseline",
                 "detail": "no metric overlap with baseline"}]
        elif report is not None and "regression_error" in report:
            primary["regression_error"] = report["regression_error"][:200]
        else:
            try:
                verdicts = obs.gate_results(results, baseline,
                                            tolerance=tol)
                primary["regression"] = verdicts or [
                    {"verdict": "no_baseline",
                     "detail": "no metric overlap with baseline"}]
            except Exception as e:  # noqa: BLE001 — gate is bookkeeping
                primary["regression_error"] = str(e)[:200]
    return primary


def _claim_print(state: dict) -> bool:
    """Atomic test-and-set on state['printed'] — the watchdog thread and
    the main thread race at the deadline boundary; exactly one may emit
    the JSON line."""
    with state["lock"]:
        if state["printed"]:
            return False
        state["printed"] = True
        return True


def _arm_deadline(state: dict) -> None:
    """Watchdog: at BENCH_DEADLINE_S (default 3600) print ONE JSON line —
    partial results if any model finished, else a structured error — and
    hard-exit, non-zero either way: the run did not finish."""
    import threading

    deadline = float(os.environ.get("BENCH_DEADLINE_S", "3600"))
    if deadline <= 0:
        return  # explicit opt-out (in-process tests drive main() directly)

    def fire():
        if not _claim_print(state):
            return
        if state["results"]:
            primary = dict(state["results"][0])
            if len(state["results"]) > 1:
                primary["extra_metrics"] = state["results"][1:]
            primary["deadline_exceeded"] = True
            if state.get("model_errors"):
                primary["model_errors"] = state["model_errors"]
            print(json.dumps(primary), flush=True)
            os._exit(2)
        print(json.dumps({
            "metric": "error", "value": 0, "unit": "none",
            "vs_baseline": None, "error": "deadline_exceeded",
            "detail": f"no model finished within {deadline:.0f}s "
                      "(backend hang?)",
        }), flush=True)
        os._exit(2)

    t = threading.Timer(deadline, fire)
    t.daemon = True
    t.start()


def main() -> None:
    if os.environ.get("BENCH_COMPILE_CACHE", "1") != "0":
        # persistent executable cache: repeated invocations share
        # compiles across processes
        from paddle_tpu.core.compiler import default_compile_cache

        sys.stderr.write(f"# compile cache: {default_compile_cache()}\n")
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    names = os.environ.get(
        "BENCH_MODELS", "resnet50,transformer,deepfm"
    )
    if names.strip() == "all":  # every wired baseline/benchmark-fluid row
        names = ("resnet50,transformer,deepfm,lstm,lenet,alexnet,"
                 "googlenet,vgg19,vgg19_infer,vgg19_infer_int8,"
                 "se_resnext,machine_translation")
    names = [m.strip() for m in names.split(",") if m.strip()]
    if not names:
        raise SystemExit("BENCH_MODELS is empty")

    amp = os.environ.get("BENCH_AMP")
    layout = os.environ.get("BENCH_LAYOUT")
    import threading

    state = {"results": [], "model_errors": [], "printed": False,
             "lock": threading.Lock()}
    _arm_deadline(state)
    model_errors = state["model_errors"]
    try:
        from paddle_tpu import observability as _obs_pkg

        _span = _obs_pkg.span
    except Exception:  # telemetry import failure must not fail models
        import contextlib

        def _span(name, **kw):
            return contextlib.nullcontext()
    try:
        # the device first: TPUPlace raises here where there is no chip
        peak_flops = _peak_flops(_bench_place().jax_device())
        for m in names:
            try:
                with _span("bench.model", model=m):
                    state["results"].append(
                        run_model(m, steps, peak_flops, amp=amp,
                                  layout=layout))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 — one model's failure
                # (e.g. a kernel lowering error) does not abort the other
                # models' measurements; it does fail the run (exit code)
                model_errors.append({
                    "model": m, "error": type(e).__name__,
                    "detail": str(e)[:800],
                })
        results = state["results"]
        if not results:
            raise RuntimeError(
                f"all models failed: {json.dumps(model_errors)[:1500]}")
        primary = dict(results[0])
        if len(results) > 1:
            primary["extra_metrics"] = results[1:]
        if model_errors:
            primary["model_errors"] = model_errors
        primary = _attach_observability(primary, results)
        if _claim_print(state):
            print(json.dumps(primary))
    except BaseException as e:  # noqa: BLE001 — the contract is ONE JSON line
        err = {
            "metric": "error",
            "value": 0,
            "unit": "none",
            "vs_baseline": None,
            "error": ("backend_unavailable"
                      if "backend" in str(e).lower()
                      or "UNAVAILABLE" in str(e) else type(e).__name__),
            "detail": str(e)[:2000],
        }
        if state["results"]:
            # some models DID finish: keep their numbers in the artifact
            err["partial_results"] = state["results"]
        if state.get("model_errors"):
            err["model_errors"] = state["model_errors"]
        if _claim_print(state):
            print(json.dumps(err))
        sys.exit(2)
    if model_errors:
        # the JSON above carries the models that finished; the run failed
        sys.exit(1)


if __name__ == "__main__":
    main()
