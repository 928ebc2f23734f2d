"""What survives a unit's recomputation (core/compiler.py: `keep`,
`rematerialised`): sparse attention's output, logsumexp and thresholds stay
from the first forward, so under the recurrence's checkpoint the backward
runs no second chunk scan (no second index, search and attend kernel); the
mathematics is the bare jax.checkpoint's and no checkpoint's; fewer than all
three kept saves nothing; a unit whose ops name nothing lowers to what the
bare jax.checkpoint gives; `recurrence.lower` counts the kept values.  The
flash sites (PR 44) keep `out` and the logsumexp where their backward is the
Pallas kernel: one forward kernel a site where the bare checkpoint has two,
the bare checkpoint's numbers bit for bit; a site on the XLA recompute
backward names nothing.  The op `kept` (PR 61) tags a value of plain ops:
the product before it stays from the first forward and leaves the
recomputation."""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from decoder_steps import once_a_program
from paddle_tpu import models, observability
from paddle_tpu.core import compiler
from paddle_tpu.kernels import sparse_attention as dsa

# (the package's `flash_attention` is the function)
fa = sys.modules["paddle_tpu.kernels.flash_attention"]

H, G, S, D, HI, DI, TOPK, TQ, TK = 4, 2, 64, 16, 3, 8, 8, 16, 8
CHUNKS = S // TQ


def _bare(fn, **kwargs):
    """The checkpoint before `rematerialised`: nothing but the inputs
    survives."""
    return jax.checkpoint(fn, **kwargs)


# the door's word (kernels/engine.py) for each engine of the op
FORCE = {"xla": "jax", "interpret": "interpret"}
WRAPS = {"none": lambda f: f,
         "bare": lambda f: _bare(f, prevent_cse=False),
         "kept": lambda f: compiler.rematerialised(f, prevent_cse=False)}


def _inputs(seed=0):
    rng = np.random.RandomState(seed)

    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    return (normal(1, S, H * D), normal(1, S, G * D), normal(1, S, G * D),
            normal(1, S, HI * DI), normal(1, S, DI), normal(1, S, HI))


def _layer(engine):
    """A fresh function a call (jax caches a checkpoint's trace by the
    function): cheap ops a layer recomputes (a scale, a tanh, the
    [B, S, H, D] transposes), the op, a loss over both its outputs."""

    def heads(x, n):
        return jnp.swapaxes(x.reshape(1, S, n, -1), 1, 2)

    def layer(q, k, v, qi, ki, w):
        q, k, v = (heads(jnp.tanh(x) * 1.5, n)
                   for x, n in ((q, H), (k, G), (v, G)))
        out, kl = dsa.sparse_attention(
            q, k, v, heads(qi, HI), ki * 0.5, w, topk=TOPK, scale=D ** -0.5,
            q_chunk=TQ, kv_chunk=TK, force=FORCE[engine])
        return jnp.sum(out * jnp.cos(out)) + 3.0 * kl

    return layer


def _count(jaxpr, pred) -> int:
    """Equations of `jaxpr`, sub-jaxprs included, that `pred` accepts."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, pred)
    return n


def _chunk_scans(fn, args) -> int:
    """lax.scans over the site's chunks in the differentiated `fn`: one the
    forward, one the backward, and one more for each forward run again."""
    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(fn, argnums=tuple(range(6))))(*args).jaxpr
    return _count(jaxpr, lambda e: e.primitive.name == "scan"
                  and e.params["length"] == CHUNKS)


@pytest.mark.parametrize("engine", ["xla", "interpret"])
def test_loss_and_gradients_are_the_bare_checkpoints_and_no_checkpoints(engine):
    args = _inputs()
    got = {}
    with jax.default_matmul_precision("highest"):
        for name, wrap in WRAPS.items():
            got[name] = jax.jit(jax.value_and_grad(
                wrap(_layer(engine)), argnums=tuple(range(6))))(*args)
    loss, grads = got["kept"]
    assert all(float(jnp.abs(g).max()) > 0 for g in grads)
    for name in ("none", "bare"):
        want_loss, want = got[name]
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
        for g, r in zip(grads, want):
            np.testing.assert_allclose(
                g, r, rtol=1e-6, atol=1e-6 * float(jnp.abs(r).max()),
                err_msg=name)


@pytest.mark.parametrize("engine", ["xla", "interpret"])
def test_the_kept_values_are_the_first_forwards_bit_for_bit(engine):
    """The residuals jax holds across the rematerialised layer include the
    `out` that the same run's forward returned, bit for bit, and the lse
    and thr of `_forward` (run apart: another executable, so the floats to
    a rounding and the integers exactly); the bare checkpoint holds none of
    the three."""
    args = _inputs(1)
    layer = _layer(engine)

    def heads(x, n):
        return jnp.swapaxes(x.reshape(1, S, n, -1), 1, 2)

    def with_out(q, k, v, qi, ki, w):
        qh, kh, vh = (heads(jnp.tanh(x) * 1.5, n)
                      for x, n in ((q, H), (k, G), (v, G)))
        out, kl = dsa.sparse_attention(
            qh, kh, vh, heads(qi, HI), ki * 0.5, w, topk=TOPK,
            scale=D ** -0.5, q_chunk=TQ, kv_chunk=TK, force=FORCE[engine])
        return jnp.sum(out * jnp.cos(out)) + 3.0 * kl, (out[0], qh[0], kh[0],
                                                        vh[0])

    with jax.default_matmul_precision("highest"):
        _, vjp, (out, q, k, v) = jax.vjp(
            compiler.rematerialised(with_out, prevent_cse=False), *args,
            has_aux=True)
        kept = jax.tree_util.tree_leaves(vjp)
        bare = jax.tree_util.tree_leaves(
            jax.vjp(WRAPS["bare"](layer), *args)[1])
        _, lse, _, thr = dsa._forward(
            q, k, v, heads(args[3], HI)[0], args[4][0] * 0.5, args[5][0],
            (TOPK, D ** -0.5, TQ, TK, engine))
    assert thr.dtype == jnp.uint32 and thr.shape == (CHUNKS, TQ)

    def like(leaves, x):
        return [np.asarray(l) for l in leaves
                if l.shape == x.shape and l.dtype == x.dtype]

    assert any(np.array_equal(l, out) for l in like(kept, out))
    assert any(np.array_equal(l, thr) for l in like(kept, thr))
    assert any(np.allclose(l, lse, rtol=1e-6, atol=1e-6)
               for l in like(kept, lse))
    assert not like(bare, out) and not like(bare, lse) and not like(bare, thr)
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in (out, lse, thr)) == dsa.kept_bytes(q[None])


@pytest.mark.parametrize("engine", ["xla", "interpret"])
def test_the_chunk_scan_of_the_forward_runs_once_a_layer(engine):
    args = _inputs()
    scans = {name: _chunk_scans(wrap(_layer(engine)), args)
             for name, wrap in WRAPS.items()}
    assert scans == {"none": 2, "bare": 3, "kept": 2}


@pytest.mark.parametrize("named", [
    ("thr",), ("lse", "thr"), ("out",), ("out", "lse"), ("out", "thr"),
    ("out", "lse", "thr")])
def test_all_three_or_nothing(monkeypatch, named):
    """Kept in part the scan still has to run for the rest: the set is not
    to be trimmed."""
    def keep_some(*values):
        return tuple(compiler.keep(v)[0] if name in named else v
                     for name, v in zip(dsa.KEPT, values))

    monkeypatch.setattr(dsa, "keep", keep_some)
    scans = _chunk_scans(WRAPS["kept"](_layer("xla")), _inputs())
    assert scans == (2 if set(named) == set(dsa.KEPT) else 3)


# ---------------------------------------------------------------------------
# the program: a layer is a one-trip recurrence under recompute_scope

SPARSE = dict(vocab_size=64, max_length=2048, n_layer=2, d_model=64,
              n_head=8, n_kv_head=2, head_dim=128, mrope_section=(16, 24, 24),
              index_heads=4, index_dim=64, index_topk=512, q_chunk=512,
              kv_chunk=512, n_routed_experts=16, experts_held=4,
              expert_offset=4, top_k=3, d_expert=64)
FLASH = {
    "looped_decoder": (models.looped_decoder, models.LoopedDecoderConfig, dict(
        vocab_size=64, max_length=512, n_layer=2, n_head=2, head_dim=128,
        d_model=64, d_inner=128)),
    "expert_decoder": (models.expert_decoder, models.ExpertDecoderConfig, dict(
        vocab_size=64, max_length=512, n_layer=3, d_model=64, d_inner=128,
        n_head=2, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=64, n_routed_experts=16, experts_held=4, expert_offset=4,
        top_k=3, d_expert=64)),
    "compressed_decoder": (
        models.compressed_decoder, models.CompressedDecoderConfig, dict(
            vocab_size=64, max_length=512, n_layer=2, d_model=64, n_head=2,
            n_kv_head=1, head_dim=128, rotary_dim=64, router_dim=16,
            n_routed_experts=8, experts_held=4, d_expert=64)),
}


def _step_for_the_tpu(build, config, rows=1,
                      span_names=("recurrence.lower", "dsa.lower",
                                  "attn.lower", "mla.lower")):
    """(the training step's StableHLO as it lowers for a TPU, no chip and
    no compiler, the Mosaic kernels serialised client-side, no source
    locations in the text; its spans called `span_names`)."""
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        spec = build(config)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = exe.capture_program(
                fluid.default_main_program(),
                feed=spec.synthetic_batch(rows, 0), fetch_list=[spec.loss])
            text = jax.jit(compiled.raw_fn).trace(
                *jax.tree_util.tree_map(
                    lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype),
                    (feed_vals, state_vals, rng))).lower(
                        lowering_platforms=("tpu",)).as_text()
        spans = {n: [dict(s.args) for s in
                     observability.default_tracer().spans() if s.name == n]
                 for n in span_names}
        return text, spans
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()


@once_a_program
def _flash_step(model, rows, recompute=True):
    """`_step_for_the_tpu` of FLASH[model] as the tree lowers it, once for
    the cases that read the same step."""
    build, config, sizes = FLASH[model]
    return _step_for_the_tpu(
        build, config(**{**sizes, "use_recompute": recompute}), rows=rows)


def _kernels(text) -> dict:
    """Calls of each Pallas kernel in a step's StableHLO (a scan's body
    stands there once, whatever its trips).  The flash pair that takes
    several batch-head rows a grid step (`_flash_rows_kernel`,
    `_flash_bwd_rows_kernel`: S 512 at these models' two rows of two heads)
    counts under the pair's own names: a forward is a forward."""
    names = [n.replace("_rows_kernel", "_kernel") for n in re.findall(
        r'kernel_name = "(_\w+_kernel)"', text)]
    return {n: names.count(n) for n in sorted(set(names))}


@pytest.fixture(scope="module")
def sparse_steps():
    """The sparse decoder's step for the TPU as the tree lowers it, and with
    `rematerialised` put back to the bare checkpoint."""
    cfg = models.SparseDecoderConfig(**SPARSE)
    steps = {"kept": _step_for_the_tpu(models.sparse_decoder, cfg)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compiler, "rematerialised", _bare)
        steps["bare"] = _step_for_the_tpu(models.sparse_decoder, cfg)
    return steps


def test_three_attend_and_three_index_kernels_a_layer_in_the_tpus_step(
        sparse_steps):
    """A layer: attend = forward, the heads' summed probabilities, backward
    (`_bwd_kernel` is both); index = forward's scoring, the backward's,
    the backward kernel.  The bare checkpoint runs `_fwd_kernel` and
    `_index_kernel` once more each."""
    layers = SPARSE["n_layer"]
    assert _kernels(sparse_steps["kept"][0]) == {
        "_fwd_kernel": layers, "_bwd_kernel": 2 * layers,
        "_index_kernel": 2 * layers, "_index_bwd_kernel": layers}
    assert _kernels(sparse_steps["bare"][0]) == {
        "_fwd_kernel": 2 * layers, "_bwd_kernel": 2 * layers,
        "_index_kernel": 3 * layers, "_index_bwd_kernel": layers}


def test_recurrence_lower_counts_three_kept_values_a_sparse_layer(
        sparse_steps):
    _, spans = sparse_steps["kept"]
    assert [(s["trips"], s["recompute"], s["bodies_lowered"], s["kept"])
            for s in spans["recurrence.lower"]] == SPARSE["n_layer"] * [
                (1, 1, 1, len(dsa.KEPT))]
    assert [(s["kept"], s["kept_bytes"]) for s in spans["dsa.lower"]] == \
        SPARSE["n_layer"] * [("out,lse,thr", 2048 * (8 * 128 * 2 + 8 * 4 + 4))]


def test_without_recompute_the_tags_do_nothing():
    """use_recompute false: the tags are there (`kept` 3) and no checkpoint
    is: the step holds no second forward either way."""
    cfg = models.SparseDecoderConfig(**{**SPARSE, "use_recompute": False})
    text, spans = _step_for_the_tpu(models.sparse_decoder, cfg)
    assert [(s["recompute"], s["kept"]) for s in spans["recurrence.lower"]] \
        == SPARSE["n_layer"] * [(0, len(dsa.KEPT))]
    assert _kernels(text)["_fwd_kernel"] == SPARSE["n_layer"]


def _flash_sites(model, config) -> int:
    """Flash sites in one body of the model's recurrence."""
    return config.n_layer if model == "looped_decoder" else 1


@pytest.mark.parametrize("model", sorted(FLASH))
def test_a_flash_site_keeps_out_and_lse_and_its_forward_is_traced_once(
        monkeypatch, model):
    """Flash sites under a recomputed trip whose backward is the Pallas
    kernel (S 512): `recurrence.lower` counts 2 kept values a site of its
    body, the op's own span names them, and the step for the TPU holds one
    `_flash_kernel` a site where the bare jax.checkpoint(body,
    prevent_cse=False) holds two (a scan's body stands in the text once);
    the backward kernels are the same."""
    build, config, sizes = FLASH[model]
    cfg = config(**sizes)
    text, spans = _flash_step(model, 2, True)
    sites = _flash_sites(model, cfg)
    assert spans["recurrence.lower"]
    assert all(s["recompute"] == 1 and s["kept"] == len(fa.KEPT) * sites
               for s in spans["recurrence.lower"])
    ops = spans["attn.lower"] + spans["mla.lower"]
    assert ops and all(s["kept"] == "out,lse" and s["kept_bytes"] > 0
                       for s in ops)
    assert "tpu_custom_call" in text
    monkeypatch.setattr(compiler, "rematerialised", _bare)
    bare, bare_spans = _step_for_the_tpu(build, cfg, rows=2)
    # the tags are there under the bare checkpoint too, and save nothing
    assert [s["kept"] for s in bare_spans["recurrence.lower"]] == \
        [s["kept"] for s in spans["recurrence.lower"]]
    kept, bare = _kernels(text), _kernels(bare)
    assert kept["_flash_kernel"] == cfg.n_layer
    assert bare["_flash_kernel"] == 2 * cfg.n_layer
    assert kept["_flash_bwd_kernel"] == bare["_flash_bwd_kernel"] \
        == cfg.n_layer


@pytest.mark.parametrize("model", sorted(FLASH))
def test_a_flash_site_on_the_xla_backward_names_nothing(monkeypatch, model):
    """S 256, one row of two heads (two 256 x 256 blocks a grid step are
    not worth one): the shape keeps the XLA recompute backward (_bwd_plan),
    whose forward emits no logsumexp: nothing is tagged, `kept` is 0 and "", and
    the step's StableHLO for the TPU is, character for character, the one
    the bare jax.checkpoint(body, prevent_cse=False) gives."""
    build, config, sizes = FLASH[model]
    cfg = config(**{**sizes, "max_length": 256})
    # both lowerings from empty tracing caches: which of a step's jitted
    # jax.numpy helpers share one private function goes by what the
    # process traced before (found when a new test file ran ahead of this
    # one: a second `_where` of the experts' counts, PR 45)
    jax.clear_caches()
    text, spans = _step_for_the_tpu(build, cfg, rows=1)
    assert spans["recurrence.lower"]
    assert all(s["recompute"] == 1 and s["kept"] == 0
               for s in spans["recurrence.lower"])
    ops = spans["attn.lower"] + spans["mla.lower"]
    assert ops and all((s["kept"], s["kept_bytes"]) == ("", 0) for s in ops)
    assert _kernels(text).get("_flash_bwd_kernel", 0) == 0
    assert "tpu_custom_call" in text
    monkeypatch.setattr(compiler, "rematerialised", _bare)
    jax.clear_caches()
    bare, _ = _step_for_the_tpu(build, cfg, rows=1)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        hashlib.sha256(bare.encode()).hexdigest()


@pytest.mark.parametrize("recompute, forwards", [(True, 2), (False, 1)])
def test_a_cca_mix_site_is_a_forward_its_recomputation_and_a_backward_kernel(
        recompute, forwards):
    """kernels/cca_mix.py's pair keeps the op's inputs and nothing else:
    under a recomputed layer the step for the TPU holds the forward kernel
    twice a layer (the layer's forward, and again for what the flash
    backward reads: q^, k^, v) and the backward kernel once; without
    recomputation once each.  (tests/test_cca_mix_kernel.py holds the
    compiled step: at most one of the two forwards is left under
    `rematted_computation`.)"""
    _, config, sizes = FLASH["compressed_decoder"]
    cfg = config(**sizes)
    text, _ = _flash_step("compressed_decoder", 2, recompute)
    kernels = _kernels(text)
    assert kernels["_cca_mix_kernel"] == forwards * cfg.n_layer
    assert kernels["_cca_mix_bwd_kernel"] == cfg.n_layer
    assert kernels["_flash_kernel"] == cfg.n_layer


def test_without_recompute_the_flash_tags_do_nothing():
    """use_recompute false: the tags are there (`kept` 2 a site) and no
    checkpoint is: one forward kernel a site either way."""
    _, config, sizes = FLASH["compressed_decoder"]
    cfg = config(**sizes)
    text, spans = _flash_step("compressed_decoder", 2, False)
    assert [(s["recompute"], s["kept"]) for s in spans["recurrence.lower"]] \
        == cfg.n_layer * [(0, len(fa.KEPT))]
    assert _kernels(text)["_flash_kernel"] == cfg.n_layer


FS, FH, FG, FD, FDV = 256, 4, 2, 32, 16   # a flash site: 4 heads on 2


def _flash_layer(force, window=None, hand_out=False):
    """A fresh function a call: cheap ops a layer recomputes, the flash
    call, a loss over its output (`hand_out`: and the call's output and
    operands beside the loss)."""

    def heads(x, n):
        return jnp.swapaxes(x.reshape(2, FS, n, -1), 1, 2)

    def layer(q, k, v):
        q, k, v = (heads(jnp.tanh(x) * 1.5, n)
                   for x, n in ((q, FH), (k, FG), (v, FG)))
        out = fa.flash_attention(q, k, v, causal=True, force=force,
                                 window=window)
        loss = jnp.sum(out * jnp.cos(out))
        return (loss, (out, q, k, v)) if hand_out else loss

    return layer


def _flash_inputs(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(2, FS, n), jnp.float32)
                 for n in (FH * FD, FG * FD, FG * FDV))


def _pallas_calls(fn, args) -> dict:
    """pallas_calls by kernel in the differentiated `fn` as traced."""
    import collections

    calls = collections.Counter()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(fn, argnums=(0, 1, 2)))(*args)
    _count(jaxpr.jaxpr, lambda e: e.primitive.name == "pallas_call"
           and calls.update([e.params["jaxpr"].debug_info.func_name]))
    return dict(calls)


@pytest.mark.parametrize("window", [None, 64])
def test_the_flash_forward_is_traced_once_under_a_rematerialised_layer(
        window):
    args = _flash_inputs()
    calls = {name: _pallas_calls(wrap(_flash_layer("interpret", window)),
                                 args) for name, wrap in WRAPS.items()}
    # under a window the band's pair (PR 59), else the block kernels
    fwd, bwd = (("_flash_kernel", "_flash_bwd_kernel") if window is None
                else ("_band_kernel", "_band_bwd_kernel"))
    once = {fwd: 1, bwd: 1}
    assert calls == {"none": once, "kept": once,
                     "bare": {**once, fwd: 2}}
    # the XLA recompute backward: no kernel, nothing to keep
    assert _pallas_calls(WRAPS["kept"](_flash_layer("jax", window)),
                         args) == {}


@pytest.mark.parametrize("window", [None, 64])
def test_flash_loss_and_gradients_are_the_bare_checkpoints_bit_for_bit(
        window):
    """The backward reads the first forward's `out` and `lse` where the bare
    checkpoint read a recomputed copy of both: the same kernel on the same
    operands, so the same bits, loss and every gradient; and no
    checkpoint's."""
    args = _flash_inputs(2)
    lowered = {name: jax.jit(jax.value_and_grad(
        wrap(_flash_layer("interpret", window)), argnums=(0, 1, 2))).lower(
            *args) for name, wrap in WRAPS.items()}
    # no pass over the kept `out`: what jax puts on a saved float
    assert "reduce_precision" not in lowered["kept"].as_text()
    got = {name: low.compile()(*args) for name, low in lowered.items()}
    loss, grads = got["kept"]
    assert all(float(jnp.abs(g).max()) > 0 for g in grads)
    for name in ("none", "bare"):
        want_loss, want = got[name]
        assert np.array_equal(loss, want_loss), name
        for g, r in zip(grads, want):
            assert np.array_equal(g, r), name


def test_the_kept_flash_values_are_the_first_forwards_bit_for_bit():
    """The residuals jax holds across the rematerialised layer include the
    `out` the same run's forward returned, as its bits (an integer array:
    jax puts no reduce_precision pass on one), and the packed lse, bit for
    bit against the kernel run apart on the same operands; the bare
    checkpoint holds neither; `kept_bytes` is their size."""
    args = _flash_inputs(1)
    _, vjp, (out, q, k, v) = jax.vjp(
        compiler.rematerialised(_flash_layer("interpret", hand_out=True),
                                prevent_cse=False), *args, has_aux=True)
    kept = jax.tree_util.tree_leaves(vjp)
    bare = jax.tree_util.tree_leaves(
        jax.vjp(WRAPS["bare"](_flash_layer("interpret")), *args)[1])
    klen = jnp.full((2,), FS, jnp.float32)
    _, lse = fa._pallas_flash(q, k, v, klen, True, FD ** -0.5,
                              interpret=True)

    def like(leaves, x):
        return [np.asarray(l) for l in leaves
                if l.shape == x.shape and l.dtype == x.dtype]

    bits = np.asarray(out).view(np.uint32)
    assert any(np.array_equal(l, bits) for l in like(kept, bits))
    assert any(np.array_equal(l, lse) for l in like(kept, lse))
    assert not like(kept, out)
    assert not like(bare, bits) and not like(bare, out) \
        and not like(bare, lse)
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in (out, lse)) == fa.kept_bytes(q, v)
    assert fa.kept(q, k, v, True, force="interpret") == fa.KEPT
    assert fa.kept(q, k, v, True, force="jax") == ()


def _op_step_chunk_scans(recompute: bool) -> tuple:
    """(chunk scans in the step, its loss and gradients) of a program whose
    one sparse_attention op stands bare in block 0, under recompute_scope
    or not: no recurrence, the compiler's own checkpoint around the op."""
    import contextlib

    from paddle_tpu import layers

    fluid.reset_default_env()
    rng = np.random.RandomState(3)
    shapes = dict(q=(1, H, S, D), k=(1, G, S, D), v=(1, G, S, D),
                  qi=(1, HI, S, DI), ki=(1, S, DI), w=(1, S, HI))
    ps = {n: layers.create_parameter(list(shape), "float32", name=n)
          for n, shape in shapes.items()}
    with (fluid.recompute_scope() if recompute else contextlib.nullcontext()):
        out, kl = layers.sparse_attention(
            ps["q"], ps["k"], ps["v"], ps["qi"], ps["ki"], ps["w"],
            topk=TOPK, q_chunk=TQ, kv_chunk=TK)
    loss = layers.elementwise_add(
        layers.reduce_sum(layers.square(out)), layers.scale(kl, scale=3.0))
    pairs = fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for n, shape in shapes.items():
        fluid.global_scope().set_var(n, rng.randn(*shape).astype(np.float32))
    fetch = [loss] + [g for _, g in pairs]
    compiled, feed_vals, state_vals, key = exe.capture_program(
        fluid.default_main_program(), feed={}, fetch_list=fetch)
    jaxpr = jax.make_jaxpr(compiled.raw_fn)(feed_vals, state_vals, key).jaxpr
    scans = _count(jaxpr, lambda e: e.primitive.name == "scan"
                   and e.params["length"] == CHUNKS)
    return scans, [np.asarray(x) for x in exe.run(feed={}, fetch_list=fetch)]


def test_the_compilers_own_recompute_branch_keeps_what_an_op_named(
        monkeypatch):
    """An op under recompute_scope that is no recurrence goes through
    `_lower_forward_op`'s checkpoint, the same `rematerialised`: one
    forward scan and one backward, as with no checkpoint, and the same
    numbers; put back to the bare checkpoint it runs the forward twice."""
    plain_scans, plain = _op_step_chunk_scans(recompute=False)
    scans, got = _op_step_chunk_scans(recompute=True)
    assert (plain_scans, scans) == (2, 2)
    for g, r in zip(got, plain):
        np.testing.assert_allclose(g, r, rtol=1e-6,
                                   atol=1e-6 * np.abs(r).max())
    monkeypatch.setattr(compiler, "rematerialised", _bare)
    assert _op_step_chunk_scans(recompute=True)[0] == 3


# ---------------------------------------------------------------------------
# the op `kept`: a value of plain ops that a program names

def _mlp(tag, hand_out=False):
    """A fresh function a call: a norm a layer recomputes, W1's product,
    the op `kept`'s registered lowering (or nothing) on it, silu(g) * u,
    W2's product, a loss (`hand_out`: and W1's output beside it)."""
    import types

    from paddle_tpu.core.registry import OpRegistry

    def kept(x):
        ctx = types.SimpleNamespace(cur_op=None, kept=0)
        return OpRegistry.get("kept").lower(ctx, {"X": [x]}, {})["Out"][0]

    def layer(x, w1, w2):
        x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        first = jnp.dot(x.astype(jnp.bfloat16), w1.astype(jnp.bfloat16))
        g, u = jnp.split(kept(first) if tag else first, 2, -1)
        out = jnp.dot(jax.nn.silu(g) * u, w2.astype(jnp.bfloat16))
        loss = jnp.sum(jnp.square(out.astype(jnp.float32)))
        return (loss, first) if hand_out else loss

    return layer


def _dots(fn, args, width) -> int:
    """dot_generals of the differentiated `fn` whose result is `width`
    wide."""
    jaxpr = jax.make_jaxpr(jax.value_and_grad(fn, argnums=(0, 1, 2)))(*args)
    return _count(jaxpr.jaxpr, lambda e: e.primitive.name == "dot_general"
                  and e.outvars[0].aval.shape[-1] == width)


def _mlp_inputs(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), jnp.float32)
                 for shape in ((2, 32, 16), (16, 48), (24, 16)))


def test_the_op_kept_takes_its_product_out_of_the_recomputation():
    """W1's product, [.., 2 x 24]: once with no checkpoint, twice under the
    bare one, once where the op `kept` tags its output; untagged,
    `rematerialised` is the bare checkpoint."""
    args = _mlp_inputs()
    dots = {(name, tag): _dots(wrap(_mlp(tag)), args, 48)
            for name, wrap in WRAPS.items() for tag in (True, False)}
    assert dots == {("none", True): 1, ("none", False): 1,
                    ("bare", True): 2, ("bare", False): 2,
                    ("kept", True): 1, ("kept", False): 2}


def test_the_value_the_op_kept_holds_is_the_first_forwards_bit_for_bit():
    """The residuals jax holds across the rematerialised layer include the
    bf16 [.., 48] array W1's product returned in the same run, bit for bit;
    untagged, and under the bare checkpoint, none of that shape is held;
    loss and gradients are the untagged layer's and no checkpoint's."""
    args = _mlp_inputs(1)
    _, vjp, first = jax.vjp(compiler.rematerialised(
        _mlp(True, hand_out=True), prevent_cse=False), *args, has_aux=True)
    assert first.dtype == jnp.bfloat16

    def like(leaves):
        return [np.asarray(l) for l in jax.tree_util.tree_leaves(leaves)
                if l.shape == first.shape and l.dtype == first.dtype]

    assert any(np.array_equal(l, first) for l in like(vjp))
    assert not like(jax.vjp(WRAPS["kept"](_mlp(False)), *args)[1])
    assert not like(jax.vjp(WRAPS["bare"](_mlp(True)), *args)[1])
    got = {(name, tag): jax.jit(jax.value_and_grad(
        wrap(_mlp(tag)), argnums=(0, 1, 2)))(*args)
        for name, wrap in WRAPS.items() for tag in (True, False)}
    loss, grads = got["kept", True]
    assert all(float(jnp.abs(g).max()) > 0 for g in grads)
    for key, (want_loss, want) in got.items():
        assert np.array_equal(loss, want_loss), key
        for g, r in zip(grads, want):
            np.testing.assert_allclose(g, r, rtol=1e-6, atol=0, err_msg=key)
