"""The weight-tied recurrence (layers.Recurrence / the `recurrence` op), the
decoder-only looped LM built on it (models/looped_decoder.py) and its two new
ops, at a small size on the CPU in fp32, against the benchmark's plain
reference (benchmark/configs/ouro-2.6b.reference.py) and against numpy."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from decoder_steps import as_one_compile, once_a_program
from paddle_tpu import layers, models, observability

# the module: models/__init__.py exports the function under the same name
ld = sys.modules["paddle_tpu.models.looped_decoder"]

from op_test import OpTest

# fp32 on the CPU against an fp32 reference that orders its sums otherwise
# (one scan body against a Python loop, flash-style attention against a
# plain softmax): rounding only, 1e-6 relative; the room is a factor ten
RTOL, ATOL = 2e-5, 2e-6

TINY = dict(vocab_size=48, max_length=12, n_layer=2, n_head=2, head_dim=8,
            d_model=16, d_inner=24)


def _reference():
    from benchmark.harness import manifest

    return manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", "ouro-2.6b.reference.py"))


def _ref_cfg(cfg):
    return {"num_attention_heads": cfg.n_head, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "total_ut_steps": cfg.loop_steps, "num_hidden_layers": cfg.n_layer,
            "exit_gate": cfg.exit_gate, "entropy_beta": cfg.entropy_beta}


def _batch(cfg, rows, seed):
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(rows, cfg.max_length + 1)).astype(np.int64)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def _build(build=models.looped_decoder, values=None, **over):
    """(spec, {parameter: value}, batch, {parameter: gradient}, loss) of one
    forward and backward pass of the program, no optimizer; `values` in
    place of the start-up program's draws."""
    fluid.reset_default_env()
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    cfg = models.LoopedDecoderConfig(**{**TINY, **over})
    spec = build(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    # norm scales start at 1 and the gate's bias at 0: move them, so that a
    # scale or a bias that is not applied shows
    rng = np.random.RandomState(11)
    for p, _ in pairs:
        if p.name.endswith("_scale") or p.name == "gate_b":
            v = np.asarray(scope.find_var(p.name))
            scope.set_var(p.name, (v + 0.3 * rng.randn(*v.shape)).astype(
                np.float32))
    for name, v in (values or {}).items():
        scope.set_var(name, v)
    params = {p.name: np.asarray(scope.find_var(p.name)) for p, _ in pairs}
    batch = _batch(cfg, 3, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


_built = once_a_program(_build)


def _reference_loss_and_grad(spec, params, batch, micro=1, **cfg_over):
    cfg = {**_ref_cfg(spec.extras["config"]), **cfg_over}
    loss, grad = as_one_compile(
        _reference().loss_and_grad, params, batch, cfg,
        tuple(spec.feed_names), frozenset(params), micro)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


def _assert_same_gradients(got, want):
    assert set(got) == set(want)
    for name in sorted(want):
        scale = np.abs(want[name]).max()
        np.testing.assert_allclose(got[name], want[name], rtol=RTOL,
                                   atol=ATOL * max(scale, 1.0), err_msg=name)


@pytest.mark.parametrize("trips,gate,micro", [(4, True, 1), (4, True, 3),
                                              (1, False, 1), (3, False, 1)])
def test_program_against_the_plain_reference(trips, gate, micro):
    """Loss and every gradient; `micro` parts of the batch give the
    reference the same answer as the whole."""
    spec, params, batch, grads, loss = _built(loop_steps=trips,
                                              exit_gate=gate)
    ref_loss, ref_grads = _reference_loss_and_grad(spec, params, batch, micro)
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    _assert_same_gradients(grads, ref_grads)


def test_a_tied_weights_gradient_is_the_sum_over_untied_copies():
    """R untied copies of the stack in the reference, one for each trip:
    the program's one gradient of a tied weight is the sum of theirs."""
    import jax
    import jax.numpy as jnp

    ref = _reference()
    spec, params, batch, grads, _ = _build(loop_steps=3)
    cfg = _ref_cfg(spec.extras["config"])
    tokens, labels = (jnp.asarray(batch[n]) for n in spec.feed_names)
    tied = sorted(n for n in params if n.startswith("l") or n == "final_scale")
    assert len(tied) == 2 * 11 + 1

    def loss(copies):
        h = jnp.take(jnp.asarray(params["embed"]), tokens, axis=0)
        ces, lams = [], []
        for copy in copies:
            p = {**{k: jnp.asarray(v) for k, v in params.items()}, **copy}
            for i in range(cfg["num_hidden_layers"]):
                h = ref._layer(p, h, i, cfg)
            h = ref._rms_norm(h, p["final_scale"], cfg["rms_norm_eps"])
            ce, lam = ref._head(p, h, labels, True)
            ces.append(ce)
            lams.append(lam)
        left, total = jnp.ones_like(ces[0]), 0.0
        probs = []
        for lam in lams[:-1]:
            probs.append(lam * left)
            left = left * (1.0 - lam)
        probs.append(left)
        for q, ce in zip(probs, ces):
            total = total + q * ce + cfg["entropy_beta"] * q * jnp.log(q)
        return jnp.mean(total)

    copies = [{n: jnp.asarray(params[n]) for n in tied} for _ in range(3)]
    per_trip = jax.grad(loss)(copies)
    for n in tied:
        each = [np.asarray(g[n]) for g in per_trip]
        assert all(np.abs(g).max() > 0 for g in each), n
        total = sum(each)
        np.testing.assert_allclose(
            grads[n], total, rtol=RTOL,
            atol=ATOL * max(np.abs(total).max(), 1.0), err_msg=n)
        # and it is not one trip's alone
        assert not np.allclose(grads[n], each[-1], rtol=1e-2, atol=0)


def _unrolled(cfg):
    """The same model with the trips written out in Python: the stack's
    layers are appended R times and read the same parameters by name."""
    S = cfg.max_length
    tokens = layers.data("tokens", [S], dtype="int64")
    labels = layers.data("labels", [S], dtype="int64")
    b = ld._Builder(cfg)
    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=fluid.ParamAttr(name="embed",
                                                    initializer=b.init))
    states = []
    for _ in range(cfg.loop_steps):
        h = b.stack(h)
        states.append(h)
    loss, _, _ = ld._heads_and_loss(b, layers.stack(states, axis=0), labels)
    return models.ModelSpec(
        name="unrolled", feed_names=[tokens.name, labels.name], loss=loss,
        extras={"config": cfg})


def test_scan_lowering_against_an_unrolled_build():
    spec, params, batch, grads, loss = _built(loop_steps=4, exit_gate=True)
    # the unrolled start-up program draws each tied weight four times
    _, params_u, _, grads_u, loss_u = _build(_unrolled, values=params,
                                             loop_steps=4)
    assert set(params_u) == set(params)
    assert loss == pytest.approx(loss_u, rel=RTOL)
    _assert_same_gradients(grads, grads_u)
    # the unrolled program has the stack four times, the recurrence once
    ops = [op.type for op in fluid.default_main_program().global_block().ops]
    assert "recurrence" not in ops and ops.count("rms_norm") == 4 * 9


def test_exit_distribution_sums_to_one_and_a_shut_gate_leaves_the_last_trip():
    spec, params, batch, _, _ = _build(loop_steps=4)
    exe = fluid.Executor(fluid.CPUPlace())
    p = np.asarray(exe.run(feed=batch,
                           fetch_list=[spec.extras["exit_distribution"]])[0])
    assert p.shape == (4, 3, TINY["max_length"])
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert (p > 0).all() and p[0].std() > 0
    # gates shut (bias -30): all the mass on the last trip, no entropy left
    fluid.global_scope().set_var("gate_b", np.full([1], -30.0, np.float32))
    p, loss = exe.run(feed=batch, fetch_list=[
        spec.extras["exit_distribution"], spec.loss])
    np.testing.assert_allclose(np.asarray(p)[-1], 1.0, atol=1e-6)
    last_ce, _ = _reference_loss_and_grad(spec, params, batch,
                                          exit_gate=False)
    assert float(np.ravel(loss)[0]) == pytest.approx(last_ce, rel=1e-5)


def test_recompute_by_trip_changes_no_number():
    _, _, _, grads_on, loss_on = _build(use_recompute=True)
    _, _, _, grads_off, loss_off = _build(use_recompute=False)
    assert loss_on == pytest.approx(loss_off, rel=1e-6)
    _assert_same_gradients(grads_on, grads_off)


@pytest.mark.parametrize("recompute", [True, False])
def test_the_trip_is_the_unit_of_recomputation(recompute):
    """jax.checkpoint goes around the scan body and nowhere else: the
    step's jaxpr holds one scan over the trips whose body is (or is not) a
    remat region, and no remat region around the scan."""
    import jax

    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(
        **TINY, use_recompute=recompute))
    fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled, feed_vals, state_vals, rng = exe.capture_program(
        feed=spec.synthetic_batch(2), fetch_list=[spec.loss])
    jaxpr = jax.make_jaxpr(compiled.raw_fn)(feed_vals, state_vals, rng).jaxpr

    def names_under(jp):
        out = set()
        for eqn in jp.eqns:
            out.add(eqn.primitive.name)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    out |= names_under(inner)
        return out

    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert scans and all(e.params["length"] == 4 for e in scans)
    assert "remat2" not in [e.primitive.name for e in jaxpr.eqns]
    in_body = set().union(*(names_under(e.params["jaxpr"].jaxpr)
                            for e in scans))
    assert ("remat2" in in_body) == recompute


def test_one_gradient_and_one_optimizer_op_for_each_parameter():
    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(**TINY))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    block = fluid.default_main_program().global_block()
    params = [p.name for p in fluid.default_main_program().all_parameters()]
    assert len(params) == 2 * 11 + 1 + 4      # layers, N_f, tables and gate
    adam = [op for op in block.desc.ops if op.type == "adam"]
    assert sorted(op.input("Param")[0] for op in adam) == sorted(params)
    # no gradient was renamed and summed: the recurrence's own vjp sums the
    # trips, and nothing outside the body reads a tied weight
    assert not any(n.startswith(p + "@GRAD@RENAME@") for p in params
                   for op in block.desc.ops for n in op.output_arg_names())
    assert [op.type for op in block.desc.ops].count("recurrence") == 1
    body = fluid.default_main_program().block(1)
    assert [op.type for op in body.desc.ops].count("rms_norm") == 9


def test_recurrence_lower_span_counts_one_body(monkeypatch):
    observability.reset()
    monkeypatch.setitem(fluid.flags._VALUES, "FLAGS_observability", True)
    _build(loop_steps=4)
    spans = [s for s in observability.default_tracer().spans()
             if s.name == "recurrence.lower"]
    assert len(spans) == 1
    assert spans[0].args == {"trips": 4, "bodies_lowered": 1, "recompute": 1,
                             "kept": 0}
    assert observability.default_registry().counter(
        "recurrence_unrolled").value(op="while") == 0
    observability.reset()


@pytest.mark.parametrize("length, kept", [(512, 2), (256, 0)])
def test_recurrence_lower_counts_what_the_flash_sites_of_the_body_keep(
        monkeypatch, length, kept):
    """Lowered for the TPU (abstractly: nothing compiles): a site whose
    backward is the Pallas kernel (S 512) keeps its output and logsumexp
    through the trip's recomputation, `kept` 2 a site of the body; at S 256
    the shape keeps the XLA recompute backward and the body names nothing,
    as on the CPU (the test above)."""
    import jax

    observability.reset()
    monkeypatch.setitem(fluid.flags._VALUES, "FLAGS_observability", True)
    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(
        **{**TINY, "max_length": length, "loop_steps": 4}))
    fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    observability.reset()
    with fluid.flags.tpu_trace_scope(True):
        compiled, *args = exe.capture_program(
            fluid.default_main_program(), feed=spec.synthetic_batch(1, 0),
            fetch_list=[spec.loss])
        jax.eval_shape(compiled.raw_fn, *args)
    spans = observability.default_tracer().spans()
    assert [s.args for s in spans if s.name == "recurrence.lower"] == [
        {"trips": 4, "bodies_lowered": 1, "recompute": 1,
         "kept": kept * TINY["n_layer"]}]
    sites = [s.args for s in spans if s.name == "attn.lower"]
    assert sites and all(
        (s["kept"], s["kept_bytes"]) == (
            ("out,lse", TINY["n_head"] * length * (TINY["head_dim"] * 2 + 4))
            if kept else ("", 0)) for s in sites)
    observability.reset()


def test_a_step_trains_through_the_executor_and_the_loss_falls():
    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(**TINY))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = spec.synthetic_batch(4, seed=1)
    losses = [float(np.ravel(exe.run(feed=batch, fetch_list=[spec.loss])[0])[0])
              for _ in range(12)]
    assert losses[0] == pytest.approx(np.log(TINY["vocab_size"]), rel=0.05)
    assert losses[-1] < losses[0] - 0.3 and np.isfinite(losses).all()
    assert np.asarray(exe.run(feed=batch, fetch_list=[spec.loss])[0]).dtype \
        == np.float32


# ---------------------------------------------------------------------------
# layers.Recurrence by itself
# ---------------------------------------------------------------------------
def test_recurrence_with_two_carries_outputs_and_finals():
    fluid.reset_default_env()
    x = layers.data("x", [4], dtype="float32")
    w = layers.create_parameter([4, 4], "float32", name="w")
    zeros = layers.scale(x, scale=0.0)
    rec = layers.Recurrence(trips=3)
    with rec.block():
        a = rec.carry(x)
        n = rec.carry(zeros)
        a_new = layers.tanh(layers.matmul(a, w))
        rec.update(a, a_new)
        rec.update(n, layers.scale(n, scale=1.0, bias=1.0))
        rec.output(a_new, n)
    ys, ns = rec()
    loss = layers.mean(layers.elementwise_add(
        layers.reduce_sum(ys, dim=0), rec.final(a)))
    pairs = fluid.append_backward(loss)
    assert [p.name for p, _ in pairs] == ["w"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).randn(2, 4).astype(np.float32)
    wv = np.asarray(fluid.global_scope().find_var("w"))
    got_ys, got_ns, got_a, got_n, got_g = (np.asarray(v) for v in exe.run(
        feed={"x": xv}, fetch_list=[ys, ns, rec.final(a), rec.final(n),
                                    pairs[0][1]]))
    want, h = [], xv
    for _ in range(3):
        h = np.tanh(h @ wv)
        want.append(h)
    np.testing.assert_allclose(got_ys, np.stack(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_a, want[-1], rtol=1e-5, atol=1e-6)
    # the body sees the carry before its update: 0, 1, 2; then 3 is left
    np.testing.assert_array_equal(got_ns, np.stack(
        [np.full_like(xv, t) for t in range(3)]))
    np.testing.assert_array_equal(got_n, np.full_like(xv, 3.0))
    # the gradient of w by central differences of the numpy model
    def f(wm):
        h, total = xv.astype(np.float64), 0.0
        for _ in range(3):
            h = np.tanh(h @ wm)
            total = total + h
        return (total + h).mean()
    num = np.zeros_like(wv, dtype=np.float64)
    for i in range(4):
        for j in range(4):
            d = np.zeros((4, 4))
            d[i, j] = 1e-5
            num[i, j] = (f(wv + d) - f(wv - d)) / 2e-5
    np.testing.assert_allclose(got_g, num, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("misuse,words", [
    ("no_update", "update()"), ("no_carry", "carry()"),
    ("outside", "outside rec.block()"), ("zero_trips", "at least one trip"),
    ("foreign_update", "not created by carry()"),
    ("init_in_body", "made inside the body")])
def test_recurrence_misuse_is_an_error(misuse, words):
    fluid.reset_default_env()
    x = layers.data("x", [4], dtype="float32")
    with pytest.raises((RuntimeError, ValueError), match=words.replace(
            "(", r"\(").replace(")", r"\)")):
        if misuse == "zero_trips":
            layers.Recurrence(trips=0)
        rec = layers.Recurrence(trips=2)
        if misuse == "outside":
            rec.carry(x)
        with rec.block():
            if misuse == "no_carry":
                layers.scale(x, scale=2.0)
            elif misuse == "init_in_body":
                rec.carry(layers.scale(x, scale=2.0))
            else:
                h = rec.carry(x)
                if misuse == "foreign_update":
                    rec.update(x, h)
                elif misuse != "no_update":
                    rec.update(h, h)


def test_a_while_that_cannot_scan_says_so(monkeypatch):
    """The fallback from lax.scan to unrolling is counted and leaves the
    exception's text on a span."""
    from paddle_tpu.ops import control_flow_ops as cf

    observability.reset()
    monkeypatch.setitem(fluid.flags._VALUES, "FLAGS_observability", True)

    def refuse(*a, **k):
        raise cf._ScanFallback("array a: read in-loop while empty")

    monkeypatch.setattr(cf, "_while_scan", refuse)
    fluid.reset_default_env()
    x = layers.data("x", [20, 3], dtype="float32", append_batch_size=False)
    boot = layers.scale(layers.reduce_sum(x, dim=0), scale=0.0)
    rnn = layers.StaticRNN()
    with rnn.step():
        word = rnn.step_input(x)
        prev = rnn.memory(init=boot)
        cur = layers.elementwise_add(prev, word)
        rnn.update_memory(prev, cur)
        rnn.step_output(cur)
    out = rnn()
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.random.RandomState(0).randn(20, 3).astype(np.float32)
    got = np.asarray(exe.run(feed={"x": xv}, fetch_list=[out])[0])
    np.testing.assert_allclose(got, np.cumsum(xv, axis=0), rtol=1e-5,
                               atol=1e-5)
    assert observability.default_registry().counter(
        "recurrence_unrolled").value(op="while") == 1
    spans = [s for s in observability.default_tracer().spans()
             if s.name == "recurrence.unrolled"]
    assert len(spans) == 1 and spans[0].args["trips"] == 20
    assert "read in-loop while empty" in spans[0].args["why"]
    assert "recurrence_unrolled_total" in \
        observability.default_registry().to_prometheus()
    observability.reset()


# ---------------------------------------------------------------------------
# the two ops against numpy
# ---------------------------------------------------------------------------
class TestRmsNormOp(OpTest):
    op_type = "rms_norm"

    def setup(self, scale=True):
        rng = np.random.RandomState(3)
        x = rng.randn(3, 5, 8).astype("float32")
        s = (1.0 + 0.5 * rng.randn(8)).astype("float32")
        y = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                        + 1e-6)
        self.inputs = {"X": x, "Scale": s} if scale else {"X": x}
        self.attrs = {"epsilon": 1e-6, "begin_norm_axis": 2}
        self.outputs = {"Y": (y * s if scale else y).astype("float32")}

    @pytest.mark.parametrize("scale", [True, False])
    def test_output(self, scale):
        self.setup(scale)
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.setup()
        self.check_grad(["X", "Scale"], "Y", max_relative_error=0.01)


def _numpy_rotary(x, base, offset=0):
    half = x.shape[-1] // 2
    inv = base ** (-np.arange(half, dtype=np.float64) * 2.0 / x.shape[-1])
    ang = (np.arange(x.shape[-2], dtype=np.float64) + offset)[:, None] * inv
    x1, x2 = x[..., :half].astype(np.float64), x[..., half:].astype(np.float64)
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


class TestRotaryEmbeddingOp(OpTest):
    op_type = "rotary_embedding"

    def setup(self, base=1e6, offset=0):
        x = np.random.RandomState(4).randn(2, 3, 6, 8).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"base": base, "offset": offset}
        self.outputs = {"Out": _numpy_rotary(x, base, offset).astype(
            "float32")}

    @pytest.mark.parametrize("base,offset", [(1e6, 0), (1e4, 0), (1e6, 2000)])
    def test_output(self, base, offset):
        self.setup(base, offset)
        self.check_output(atol=2e-4 if offset else 1e-5)

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out", max_relative_error=0.01)


def test_rotary_keeps_norms_and_depends_on_relative_position_only():
    """What makes it a position embedding: each pair is turned, not scaled,
    and <rot(q, m), rot(k, n)> depends on m - n alone."""
    rng = np.random.RandomState(0)
    q, k = rng.randn(1, 1, 1, 16), rng.randn(1, 1, 1, 16)
    dots = [float((_numpy_rotary(q, 1e6, m) * _numpy_rotary(k, 1e6, n)).sum())
            for m, n in [(5, 2), (105, 102), (1005, 1002)]]
    assert dots[0] == pytest.approx(dots[1]) == pytest.approx(dots[2])
    assert np.linalg.norm(_numpy_rotary(q, 1e6, 77)) == pytest.approx(
        np.linalg.norm(q))


def test_new_ops_keep_bf16_in_bf16_out_with_fp32_inside():
    """AMP placement: statistics and angles in fp32, the output in the
    input's dtype."""
    import jax.numpy as jnp

    from paddle_tpu.core.registry import OpRegistry

    x = jnp.asarray(np.random.RandomState(1).randn(2, 2, 2048, 8) * 3,
                    jnp.bfloat16)
    s = jnp.ones([8], jnp.float32)
    y = OpRegistry.get("rms_norm").lower(
        None, {"X": [x], "Scale": [s]}, {"begin_norm_axis": 3})["Y"][0]
    r = OpRegistry.get("rotary_embedding").lower(
        None, {"X": [x]}, {"base": 1e6})["Out"][0]
    assert y.dtype == jnp.bfloat16 and r.dtype == jnp.bfloat16
    xf = np.asarray(x.astype(jnp.float32))
    want = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(y.astype(jnp.float32)), want,
                               rtol=1e-2, atol=1e-2)
    # at position 2047 a bf16 angle would be off by whole radians; the fp32
    # one leaves only the output's own rounding
    np.testing.assert_allclose(np.asarray(r.astype(jnp.float32)),
                               _numpy_rotary(xf, 1e6), rtol=1e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# what a 407 M-parameter start-up program taught: only small values fold
# ---------------------------------------------------------------------------
def test_a_large_fill_is_not_folded_into_the_program_as_a_constant():
    """An optimizer's moments are filled in the start-up program: a large
    one has to stay an operation of the program (a broadcast), a counter
    has to stay concrete so that loops keep their static trip counts."""
    import jax

    from paddle_tpu.core.compiler import CompiledBlock, _FOLD_MAX_ELEMENTS

    fluid.reset_default_env()
    side = int(np.sqrt(_FOLD_MAX_ELEMENTS)) + 1
    big = layers.fill_constant([side, side], "float32", 0.0)
    small = layers.fill_constant([1], "int64", 3)
    compiled = CompiledBlock(fluid.default_main_program(), 0, [],
                             [big.name, small.name], [])
    seen = {}

    def probe(key):
        (b, s), _, _ = compiled.raw_fn((), (), key)
        seen["big"], seen["small"] = b, s
        return b

    jaxpr = jax.make_jaxpr(probe)(jax.random.PRNGKey(0))
    assert isinstance(seen["big"], jax.core.Tracer)
    assert not isinstance(seen["small"], jax.core.Tracer)
    assert int(np.asarray(seen["small"])[0]) == 3
    assert not any(np.size(c) > _FOLD_MAX_ELEMENTS for c in jaxpr.consts)
    got = fluid.Executor(fluid.CPUPlace()).run(fetch_list=[big, small])
    assert np.asarray(got[0]).shape == (side, side) and not got[0].any()


def test_the_recurrence_trains_data_parallel_on_a_mesh():
    """ParallelExecutor over four (virtual) devices: the same losses as one
    device on the same batch, step after step."""
    import jax

    from paddle_tpu.parallel import ParallelExecutor, make_mesh

    def losses(parallel):
        fluid.reset_default_env()
        fluid.default_startup_program().random_seed = 3
        spec = models.looped_decoder(models.LoopedDecoderConfig(
            **TINY, loop_steps=3))
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        if parallel:
            exe = ParallelExecutor(
                loss_name=spec.loss.name,
                mesh=make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        batch = spec.synthetic_batch(8, seed=2)
        return [float(np.ravel(np.asarray(
            exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
            for _ in range(4)]

    one, four = losses(False), losses(True)
    np.testing.assert_allclose(four, one, rtol=1e-5)
    assert four[-1] < four[0]
