"""models/windowed_decoder.py (Mellum2-12B-A2.5B's language model: sliding
and full grouped-query attention in a pattern, YaRN on the full layers, a
softmax top-k expert block as a chip's share) against its plain reference,
benchmark/configs/mellum2-12b-a2.5b.reference.py, at tiny sizes on the CPU;
the flash kernels with `window` (the band, PR 59) and grouped K/V through
the Pallas interpreter, the long row's backward in chunks; the plans' static
counts against brute force; the shares against the uncut layer; every mutant
tools/mellum_reference_probe.py holds the chip's first step to, refused."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from decoder_steps import as_one_compile, once_a_program
from benchmark.harness import manifest
from benchmark.harness import reference as harness_reference
from paddle_tpu import models, observability
from paddle_tpu.ops import attention_ops, moe_ops

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

# a window (12) shorter than the sequence (48), positions past the YaRN
# original length (16), a ramp of several pairs (theta 100 at head 16)
YARN = dict(factor=4.0, original_length=16, beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.1386294361119891)
TINY = dict(vocab_size=64, max_length=48, d_model=32, n_head=4, n_kv_head=2,
            head_dim=16, layer_types=("sliding", "sliding", "sliding", "full"),
            sliding_window=12, rope_theta=100.0, yarn=YARN,
            n_routed_experts=16, experts_held=4, expert_offset=4, top_k=3,
            d_expert=24, residual_init_layers=28)
RTOL, ATOL = 2e-4, 2e-5


def _reference():
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "configs", "mellum2-12b-a2.5b.reference.py"))


def _ref_cfg(cfg: models.WindowedDecoderConfig, query_block=16) -> dict:
    names = {"sliding": "sliding_attention", "full": "full_attention"}
    full = {"rope_type": "default", "rope_theta": cfg.rope_theta}
    if cfg.yarn:
        full = {"rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": cfg.yarn["factor"],
                "original_max_position_embeddings":
                    cfg.yarn["original_length"],
                "beta_fast": cfg.yarn["beta_fast"],
                "beta_slow": cfg.yarn["beta_slow"],
                "attention_factor": cfg.yarn["attention_factor"]}
    return {
        "num_hidden_layers": cfg.n_layer, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_head, "max_length": cfg.max_length,
        "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "layer_types": [names[k] for k in cfg.layer_types],
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {
            "full_attention": full,
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        "rms_norm_eps": cfg.rms_norm_eps, "num_experts": cfg.experts_held,
        "router_experts": cfg.n_routed_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "train_router": cfg.train_router,
        "reference": {"query_block": query_block}}


def _build(rows=2, **over):
    """(spec, params, batch, gradients, loss) of one forward-backward pass
    of a tiny model through the Executor: the norms' scales moved off their
    starts so that one that is not applied shows, and q, k, o and the router
    with opinions, so that where a query looks, by which rotary and through
    which router rule all show in the gradient."""
    fluid.reset_default_env()
    cfg = models.WindowedDecoderConfig(**{**TINY, **over})
    spec = models.windowed_decoder(cfg)
    pairs = fluid.append_backward(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    rng = np.random.RandomState(11)
    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            v = v + 0.3 * rng.randn(*v.shape)
        elif p.name.endswith(("_attn_q_w", "_attn_k_w", "_router_w")):
            v = v * 20
        elif p.name.endswith(("_attn_o_w", "_experts_down_w")):
            v = v * 100
        scope.set_var(p.name, v.astype(np.float32))
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in fluid.default_main_program().all_parameters()}
    batch = spec.synthetic_batch(rows, seed=5)
    got = exe.run(feed=batch, fetch_list=[spec.loss] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, got[1:])}
    return spec, params, batch, grads, float(np.ravel(got[0])[0])


_built = once_a_program(_build)


def _reference_loss_and_grad(spec, params, batch, trainable, ref=None,
                             **cfg_over):
    cfg = {**_ref_cfg(spec.extras["config"]), **cfg_over}
    loss, grad = as_one_compile(
        (ref or _reference()).loss_and_grad, params, batch, cfg,
        tuple(spec.feed_names), frozenset(trainable), 1)
    return float(loss), {k: np.asarray(v) for k, v in grad.items()}


def _assert_close(grads, ref_grads):
    assert set(grads) == set(ref_grads)
    for name in sorted(ref_grads):
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=RTOL,
                                   atol=ATOL * scale, err_msg=name)


@pytest.mark.parametrize("over", [
    {}, {"use_recompute": False}, {"expert_offset": 0, "experts_held": 16},
    {"yarn": None, "layer_types": ("full", "sliding")},
    {"sliding_window": 64, "norm_topk_prob": False},
    {"train_router": False}])
def test_program_against_the_plain_reference(over):
    """Loss and every parameter's gradient, with a window shorter than the
    sequence and positions past the YaRN original length."""
    spec, params, batch, grads, loss = _built(**over)
    ref_loss, ref_grads = _reference_loss_and_grad(spec, params, batch, grads)
    assert loss == pytest.approx(ref_loss, rel=RTOL)
    _assert_close(grads, ref_grads)


def test_a_layer_is_sliding_or_full():
    fluid.reset_default_env()
    with pytest.raises(ValueError, match="sliding"):
        models.windowed_decoder(models.WindowedDecoderConfig(
            **{**TINY, "layer_types": ("sliding", "linear")}))


# ---------------------------------------------------------------------------
# the mutants of tools/mellum_reference_probe.py
# ---------------------------------------------------------------------------
sys.path.insert(0, os.path.join(REPO, "tools"))
import mellum_reference_probe as probe  # noqa: E402

MUTANT_TOL = {"loss_rtol": 1e-4, "grad_cos_min": 0.9999,
              "grad_norm_rtol": 1e-3, "param_norm_factor": 1.01}


@pytest.fixture(scope="module")
def one_step():
    return _built(expert_offset=0, experts_held=16)


def _refused(step, name):
    spec, params, batch, grads, loss = step
    ref_loss, ref_grads = as_one_compile(
        probe.mutant(name), params, batch, _ref_cfg(spec.extras["config"]),
        feed_names=tuple(spec.feed_names), trainable=frozenset(grads),
        micro=1)
    prods = {k: (float(np.vdot(grads[k], ref_grads[k])),
                 float(np.vdot(grads[k], grads[k])),
                 float(np.vdot(ref_grads[k], ref_grads[k])))
             for k in grads}
    found = harness_reference.judge(loss, float(ref_loss), prods)
    return harness_reference.problems(found, MUTANT_TOL), found


@pytest.mark.parametrize("name", (None,) + probe.MUTANTS)
def test_the_reference_refuses_each_mutant(one_step, name):
    """The program against the reference is inside the rehearsal's
    tolerances, against each mutant outside at least one (fp8 matmuls
    included)."""
    problems, found = _refused(one_step, name)
    assert bool(problems) == (name is not None), (name, found)


def test_the_router_that_takes_no_gradient_still_shows_its_rule():
    """The cell's share (train_router false): no gradient for the router's
    weight, the program inside the tolerances, and sigmoid for softmax
    still refused, by what the gates do to the experts' gradients."""
    step = _build(expert_offset=0, experts_held=16, train_router=False)
    assert not any(name.endswith("_router_w") for name in step[3])
    assert not _refused(step, None)[0]
    assert _refused(step, "sigmoid_router")[0]


def test_the_mutants_are_issue_38s_and_an_unknown_one_is_an_error():
    assert probe.MUTANTS == (
        "window512", "no_window", "plain_rope_on_full",
        "attention_factor_left_out", "yarn_on_sliding", "kv_head_mod",
        "sigmoid_router", "fp8_matmuls")
    with pytest.raises(KeyError):
        probe.mutant("no_such_mutant")


# ---------------------------------------------------------------------------
# the kernels in the interpreter
# ---------------------------------------------------------------------------
def _plain_attention(q, k, v, window, klen=None, with_lse=False):
    """K and V repeated, an explicit mask, a softmax: nothing of the
    kernel's.  `klen` [B]: the keys a batch row has; a row that sees none
    gives zeros.  `with_lse`: (out, the rows' logsumexp)."""
    H, G, S, Sk = q.shape[1], k.shape[1], q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, H // G, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = jnp.arange(S)[:, None] + (Sk - S)
    u = jnp.arange(Sk)[None, :]
    mask = u <= t
    if window is not None:
        mask &= t - u < window
    mask = mask[None, None]
    if klen is not None:
        mask = mask & (u[None, None] < klen[:, None, None, None])
    s = jnp.where(mask, s, -1e30)
    p = jnp.where(mask.any(-1, keepdims=True), jax.nn.softmax(s, -1), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return (out, jax.nn.logsumexp(s, -1)) if with_lse else out


def _qkvg(seed, B, H, G, S, D, Sk=None):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, n, length, D), jnp.float32)
                 for n, length in ((H, S), (G, Sk or S), (G, Sk or S),
                                   (H, S)))


# name: (H, G, Sq, Sk, window, block or None for the plan's, k_lengths or
# None) of a windowed call the band takes (PR 59), beside the two cases the
# test had: no window (the block kernels, in chunks) and 200 of 640
BAND_CASES = {
    "no_window_in_chunks": (4, 2, 640, 640, None, 128, None),
    "200": (4, 2, 640, 640, 200, 128, None),
    "100_is_less_than_a_block": (4, 2, 640, 640, 100, 128, None),
    "one_block": (4, 4, 384, 384, 128, 128, None),
    "8_on_2_klen_cuts_the_oldest_and_the_diagonal": (
        8, 2, 512, 512, 200, 128, (300, 512)),
    "8_on_8_a_row_with_no_key": (8, 8, 256, 256, 100, 128, (0, 129)),
    "more_keys_than_queries": (2, 1, 256, 512, 100, 128, None),
    "more_keys_no_multiple_of_the_block": (2, 1, 100, 256, 60, 128, (200,)),
    "more_queries_than_keys": (2, 2, 384, 256, 100, 128, None),
    "the_plans_own_block": (2, 1, 768, 768, 200, None, (700,)),
    "a_window_of_one_key": (2, 2, 256, 256, 1, 128, None),
}


@pytest.mark.parametrize("case", sorted(BAND_CASES))
def test_windowed_grouped_kernels_against_plain_jax_in_chunks(case):
    """Forward, logsumexp, dQ, dK and dV through the plan's test door, the
    blocks pinned to 128 x 128.  No window: 4 query heads on 2 key/value
    heads over a row of 640, the backward pinned to chunks of 256 queries
    (three trips, the last ragged).  A window: the band, ONE backward call
    whatever the row (windows that are no multiple of the block, one of one
    block, k_lengths that cut a strip, Sk != Sq, grouped and not; a row with
    no key has the logsumexp -NEG_INF and no gradient)."""
    H, G, Sq, Sk, window, block, lengths = BAND_CASES[case]
    B = len(lengths or (0,))
    q, k, v, g = _qkvg(0, B, H, G, Sq, 16, Sk)
    klen = jnp.asarray(lengths or (Sk,), jnp.float32)
    scale = 16 ** -0.5
    (want, want_lse), vjp = jax.vjp(
        lambda *x: _plain_attention(*x, window, klen, True), q, k, v)
    out, lse = fa._pallas_flash(q, k, v, klen, True, scale, block_q=block,
                                block_k=block, interpret=True, window=window)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    rows = np.asarray(lse).reshape(B, H, -1)[:, :, :Sq]
    empty = np.asarray(want_lse) < -1e29
    np.testing.assert_array_equal(rows[empty], np.float32(-fa.NEG_INF))
    np.testing.assert_allclose(rows[~empty], np.asarray(want_lse)[~empty],
                               rtol=1e-5, atol=1e-5)
    chunk = 256 if window is None else None
    got = fa._pallas_flash_bwd(q, k, v, klen, out, lse, g, True, scale,
                               block_q=block, block_k=block, interpret=True,
                               window=window, chunk=chunk)
    for a, b, name in zip(got, vjp((g, jnp.zeros_like(want_lse))), "qkv"):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg="d" + name)
    if empty.any():
        assert not np.asarray(got[0])[empty].any()
    plan = fa._bwd_plan(Sq, Sk, 16, jnp.float32, True, block, block,
                        window=window, chunk=chunk, group=H // G)
    assert plan["window"] == (window or 0)
    assert (plan["chunks"], plan["form"]) == (
        (3, "blocks") if window is None else (1, "band"))
    older = _brute(Sq, Sk, plan["block_q"], plan["block_k"], window)[2]
    assert plan["skipped_window"] == older and (window or not older)


def test_the_bands_backward_takes_a_share_of_a_group_where_it_does_not_fit(
        monkeypatch):
    """The band's backward keeps a ring of dQ a head: where a K/V head's
    whole group does not fit the plan's budget a grid row takes the most
    heads that do (fp32 operands at the cell's shape: 4 of 8) and the
    shares' dK, dV are added up after the kernel; the same numbers."""
    assert fa._band_bwd_plan(16384, 16384, 128, jnp.bfloat16, None, 128,
                             1024, 8)[1] == 8
    plan, heads = fa._band_bwd_plan(16384, 16384, 128, jnp.float32, None,
                                    128, 1024, 8)
    assert (heads, plan["block_q"], plan["engine"]) == (4, 512, "pallas")
    q, k, v, g = _qkvg(2, 1, 8, 2, 512, 16)
    klen = jnp.full((1,), 512.0)
    want, vjp = jax.vjp(lambda *x: _plain_attention(*x, 200), q, k, v)
    out, lse = fa._pallas_flash(q, k, v, klen, True, 0.25, block_q=128,
                                interpret=True, window=200)
    # room for two heads' rings of the group's four
    monkeypatch.setattr(fa, "PLAN_VMEM_BUDGET", fa.band_bwd_working_set_bytes(
        128, 3, 16, 4, jnp.float32, 16, 2))
    assert fa._band_bwd_plan(512, 512, 16, jnp.float32, 128, 16, 200,
                             4)[1] == 2
    got = fa._pallas_flash_bwd(q, k, v, klen, out, lse, g, True, 0.25,
                               block_q=128, interpret=True, window=200)
    for a, b, name in zip(got, vjp(g), "qkv"):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg="d" + name)


def test_the_public_door_takes_window_and_groups_on_every_engine():
    """flash_attention(window=, fewer K/V heads) under "jax" (the CPU's
    engine) and "interpret", forward and gradient, the plans left alone; a
    window as long as the keys is no window."""
    q, k, v, g = _qkvg(1, 2, 4, 1, 300, 8)
    want, vjp = jax.vjp(lambda *x: _plain_attention(*x, 77), q, k, v)
    for force in ("jax", "interpret"):
        def attend(q, k, v):
            return fa.flash_attention(q, k, v, causal=True, window=77,
                                      force=force)

        out, got = jax.vjp(attend, q, k, v)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
        for a, b in zip(got(g), vjp(g)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        fa.flash_attention(q, k, v, causal=True, window=300, force="jax"),
        fa.flash_attention(q, k, v, causal=True, force="jax"))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="heads"):
        fa.flash_attention(q, jnp.concatenate(3 * [k], 1),
                           jnp.concatenate(3 * [v], 1))


# ---------------------------------------------------------------------------
# the plans' static counts against brute force
# ---------------------------------------------------------------------------
def _brute(sq, sk, bq, bk, window):
    """(blocks, above the diagonal, older than the window, pairs) by
    looking at every query-key pair."""
    t = np.arange(sq)[:, None] + (sk - sq)
    u = np.arange(sk)[None, :]
    under = u <= t
    inside = under if window is None else under & (t - u < window)
    nqb, nkb = -(-sq // bq), -(-sk // bk)
    above = older = 0
    for i in range(nqb):
        for j in range(nkb):
            rows, cols = slice(i * bq, (i + 1) * bq), slice(j * bk,
                                                            (j + 1) * bk)
            if not under[rows, cols].any():
                above += 1
            elif not inside[rows, cols].any():
                older += 1
    return nqb * nkb, above, older, int(inside.sum())


@pytest.mark.parametrize("sq,sk,bq,bk,window", [
    (64, 64, 16, 16, 20), (64, 64, 16, 8, 16), (64, 64, 8, 32, 9),
    (48, 80, 16, 16, 24), (64, 64, 16, 16, None), (40, 40, 16, 8, 1)])
def test_static_counts_equal_the_brute_force_count(sq, sk, bq, bk, window):
    blocks, above, older, pairs = _brute(sq, sk, bq, bk, window)
    nqb, nkb = -(-sq // bq), -(-sk // bk)
    assert fa._skipped_steps(nqb, nkb, bq, bk, sk - sq, True, window) == \
        (above, older)
    assert fa._visible_pairs(sq, sk, True, window) == pairs
    if window is not None and bq != bk:
        return      # the band cuts queries and keys into one block length
    # the band's strip (PR 59): q-block i's sub-blocks i + lo .. i + hi hold
    # every block of its row with a visible pair, and some row fills it
    if window is not None:
        band = fa._band(bq, nqb, nkb, sk - sq, window)
        rows = [[j for j in range(nkb) if _runs(i, j, sq, sk, bq, bk, window)]
                for i in range(nqb)]
        assert all(i + band.lo <= min(r) and max(r) <= i + band.hi
                   for i, r in enumerate(rows) if r)
        assert band.n == max(len(r) for r in rows) or sq % bq or sk % bk
    # the whole row as one call of the backward: the same blocks
    plan = fa._bwd_plan(sq, sk, 8, jnp.float32, True, bq, bk, window=window,
                        chunk=None if window else sq)
    assert (plan["steps"], plan["skipped_causal"], plan["skipped_window"],
            plan["chunks"]) == (blocks, above, older, 1)


def _runs(i, j, sq, sk, bq, bk, window):
    t = np.arange(i * bq, min((i + 1) * bq, sq))[:, None] + (sk - sq)
    u = np.arange(j * bk, min((j + 1) * bk, sk))[None, :]
    return bool(((u <= t) & (t - u < window)).any())


@pytest.mark.parametrize("window", [None, 24])
def test_chunked_trips_cover_every_visible_pair_once(window):
    """The outer loop's trips (no window): every query in one trip, and
    every key a trip's queries see among the keys it is handed; the counts
    add up.  Under a window there is no loop: the band's one call counts the
    blocks with a visible pair."""
    sq = sk = 96
    t = np.arange(sq)[:, None]
    u = np.arange(sk)[None, :]
    inside = (u <= t) if window is None else (u <= t) & (t - u < window)
    if window is None:
        trips = fa._bwd_trips(sq, sk, 8, jnp.float32, True, 16, 16, None,
                              chunk=32)
        assert [t[:2] for t in trips] == [(0, 32), (32, 64), (64, 96)]
        for q0, q1, k0, k1, bq, bk in trips:
            assert (bq, bk) == (16, 16) and k0 % 8 == 0
            seen = np.flatnonzero(inside[q0:q1].any(axis=0))
            assert k0 <= seen.min() and seen.max() < k1
    plan = fa._bwd_plan(sq, sk, 8, jnp.float32, True, 16, 16, window=window,
                        chunk=None if window else 32)
    assert plan["chunks"] == (1 if window else 3)
    run = plan["steps"] - plan["steps_skipped"]
    blocks = sum(bool(inside[i:i + 16, j:j + 16].any())
                 for i in range(0, sq, 16) for j in range(0, sk, 16))
    assert run == blocks


def test_the_cells_plans_at_the_real_shape():
    """S 16384, head 128, bf16, 8 query heads a K/V head: a sliding layer's
    forward walks the band a strip of three 512-row blocks a step, its
    backward is ONE call of 512 x 512 blocks, three q-blocks a k-block (PR
    59; 16 chunks of 1024 queries before); a full layer's backward 4 chunks
    of 4096; the shapes of the older cells keep their one call, their
    blocks and their engine."""
    args = (16384, 16384, 128, jnp.bfloat16, True)
    block = fa._plan_band(
        16384, 16384, 1024, lambda b, n: fa.band_fwd_working_set_bytes(
            b, n, 128, 16384 // b, jnp.bfloat16, True, 128, 8), True)
    assert block == 512
    assert fa._band(512, 32, 32, 0, 1024) == fa._Band(
        512, -2, 3, (True, False, False), (False, False, True))
    assert fa._plan_blocks(*args, True, 128) == (1024, 1024)
    sliding = fa._bwd_plan(*args, window=1024, group=8)
    assert (sliding["engine"], sliding["chunks"], sliding["block_q"],
            sliding["block_k"], sliding["form"]) == (
                "pallas", 1, 512, 512, "band")
    assert sliding["steps"] - sliding["steps_skipped"] == 32 * 3 - 3
    full = fa._bwd_plan(*args)
    assert (full["engine"], full["chunks"], full["block_q"],
            full["block_k"]) == ("pallas", 4, 512, 512)
    assert full["steps"] - full["steps_skipped"] == 32 * 33 // 2
    for old, engine in (((2048, 2048, 128), "pallas"),
                        ((256, 256, 64), "xla")):
        plan = fa._bwd_plan(*old, jnp.bfloat16, True)
        assert (plan["engine"], plan["chunks"]) == (engine, 1)


def test_no_site_compiles_scores_that_do_not_fit():
    """A site whose fp32 scores pass 2 GiB is never handed to XLA on a TPU.
    S 256 with grouped K/V, whose grid steps take one 256 x 256 block each,
    is XLA's while its scores fit and the Pallas kernel's past that, at
    those blocks (one rule, _bwd_chunk_rows'; with a K/V head a query head
    a step takes several rows, and the Pallas backward takes the site at any
    size); a head so wide that no plan fits is refused at lowering."""
    q = jax.ShapeDtypeStruct((64, 256, 256, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((64, 128, 256, 64), jnp.bfloat16)
    wide = jax.ShapeDtypeStruct((64, 256, 256, 2048), jnp.float32)
    wide_kv = jax.ShapeDtypeStruct((64, 128, 256, 2048), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    def engine(q, k, site_bh):
        return fa._bwd_plan(256, 256, q.shape[3], q.dtype, True,
                            bh=fa._packable_rows(q, k),
                            site_bh=site_bh)["engine"]

    with fluid.flags.tpu_trace_scope(True):
        assert engine(q, kv, 8192) == "xla"       # 2 GiB of scores: held
        assert engine(q, kv, 8193) == "pallas"
        assert engine(q, kv, 64 * 256) == "pallas"
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
        assert engine(wide, wide_kv, 64 * 256) == "xla"
        with pytest.raises(ValueError, match="scores"):
            jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)),
                           wide, wide_kv, wide_kv)
        assert engine(q, q, 64 * 256) == "pallas"
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


# ---------------------------------------------------------------------------
# the shares, the rotary, the spans
# ---------------------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert layer, each as the program computes it
    (moe_ops.route under the softmax rule + held_experts_part on experts
    8c .. 8c + 7 of 64), add up to the uncut reference's whole 64-expert
    block; each share alone is the reference given the same share."""
    ref = _reference()
    rng = np.random.RandomState(3)
    d, f, experts, held, k = 32, 24, 64, 8, 8
    p = {"l1_router_w": rng.randn(d, experts) * 0.5,
         "l1_experts_gate_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_up_w": rng.randn(experts, d, f) * 0.2,
         "l1_experts_down_w": rng.randn(experts, f, d) * 0.2}
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    x = jnp.asarray(rng.randn(40, d), jnp.float32)
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True,
           "router_experts": experts}
    with jax.default_matmul_precision("highest"):
        uncut = ref._expert_block(p, x, "l1", {
            **cfg, "num_experts": experts, "expert_offset": 0})
        idx, weight, _ = moe_ops.route(x, p["l1_router_w"], None, k, 1.0,
                                       True, scoring="softmax")
        total = 0.0
        for offset in range(0, experts, held):
            mine = slice(offset, offset + held)
            share = moe_ops.held_experts_part(
                x, idx, weight, p["l1_experts_gate_w"][mine],
                p["l1_experts_up_w"][mine], p["l1_experts_down_w"][mine],
                offset, experts)
            want = ref._expert_block(
                {**p, **{n: p[n][mine] for n in p if "_experts_" in n}}, x,
                "l1", {**cfg, "num_experts": held, "expert_offset": offset})
            np.testing.assert_allclose(share, want, rtol=1e-4, atol=1e-5)
            total = total + share
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


def test_yarn_is_the_formula_and_the_plain_rotary_is_what_it_was():
    """The published parameters: pairs below 18 keep their frequency, pairs
    from 35 on take a sixteenth, a linear ramp between; cos and sin carry
    attention_factor.  Without `yarn` the op is bit for bit the rotary it
    was."""
    half, theta = 64, 500000.0
    f = theta ** (-np.arange(half) * 2.0 / 128)
    got = attention_ops._yarn_inv_freq(f, 128, theta, 16.0, 8192.0, 32.0, 1.0)
    r = np.clip((np.arange(half) - 18) / (35 - 18), 0, 1)
    np.testing.assert_allclose(got, f * (1 - r) + f / 16 * r, rtol=1e-12)
    np.testing.assert_array_equal(got[:19], f[:19])
    np.testing.assert_allclose(got[35:], f[35:] / 16, rtol=1e-12)
    ref = _reference()
    cfg = manifest.read_json(os.path.join(
        REPO, "benchmark", "configs", "mellum2-12b-a2.5b.json"))
    want, factor = ref._frequencies(cfg, "full")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == 1.2772588722239782 == pytest.approx(
        0.1 * np.log(16) + 1)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 3, 40, 128),
                    jnp.float32)
    yarn = dict(factor=16.0, original_length=8192.0, beta_fast=32.0,
                beta_slow=1.0, attention_factor=factor)
    np.testing.assert_allclose(
        attention_ops._rotate(x, theta, yarn=yarn), ref._rotary(x, cfg, "full"),
        rtol=1e-5, atol=1e-5)
    # the plain path: the formula as it stood before `yarn`
    pos = np.arange(40, dtype=np.float64)
    angle = jnp.asarray(pos[:, None] * f[None, :], jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    old = jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                           x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)
    np.testing.assert_array_equal(attention_ops._rotate(x, theta), old)


def _spans_of_a_step(names, **over):
    """The named spans' counts from one training step lowered abstractly
    for the TPU (nothing compiles or runs)."""
    observability.reset()
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        cfg = models.WindowedDecoderConfig(**{**TINY, **over})
        spec = models.windowed_decoder(cfg)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = fluid.Executor(
                fluid.CPUPlace()).capture_program(
                    fluid.default_main_program(),
                    feed=spec.synthetic_batch(1, 0))
            jax.eval_shape(compiled.raw_fn, feed_vals, state_vals, rng)
        return {n: [dict(s.args) for s in
                    observability.default_tracer().spans() if s.name == n]
                for n in names}
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()


def test_attn_lower_and_the_flash_plans_say_what_a_site_was_given():
    """S 512, window 128: every site's `attn.lower` (kind, window, heads,
    pairs, rope), the forward's and backward's plans with the window, the
    K/V heads and the two kinds of skipped steps; `moe.lower` as
    keye-train-dsa16k's block reads."""
    S, W = 512, 128
    spans = _spans_of_a_step(
        ("attn.lower", "flash.plan", "flash.bwd_plan", "moe.lower"),
        max_length=S, sliding_window=W)
    keeps = dict(kept="out,lse", kept_bytes=4 * S * (16 * 2 + 4),
                 layout="bhsd")
    sliding = dict(kind="sliding", window=W, heads=4, kv_heads=2, sq=S,
                   pairs=W * (W + 1) // 2 + (S - W) * W, rope="plain",
                   **keeps)
    full = dict(kind="full", window=0, heads=4, kv_heads=2, sq=S,
                pairs=S * (S + 1) // 2, rope="yarn", **keeps)
    sites = [s for s in spans["attn.lower"]]
    assert sites and all(s in (sliding, full) for s in sites)
    assert sum(s == sliding for s in sites) == 3 * sum(
        s == full for s in sites)
    plans = spans["flash.plan"]
    assert {p["window"] for p in plans} == {0, W}
    assert all(p["kv_heads"] == 2 and p["chunks"] == 1 for p in plans)
    for p in plans:
        assert p["k_steps_skipped"] == \
            p["skipped_causal"] + p["skipped_window"]
        assert p["skipped_window"] == 0 or p["window"] == W
    bwd = spans["flash.bwd_plan"]
    assert len(bwd) == 4 and {b["window"] for b in bwd} == {0, W}
    for b in bwd:
        want = dict(fa._bwd_plan(S, S, 16, jnp.bfloat16, True,
                                 window=b["window"] or None, group=2),
                    kv_heads=2)
        assert b == want and b["engine"] == "pallas" and b["chunks"] == 1
        assert b["form"] == ("band" if b["window"] else "blocks")
    assert len(spans["moe.lower"]) >= 4
    assert all(m["scoring"] == "softmax" and m["experts_held"] == 4
               and m["experts_total"] == 16 for m in spans["moe.lower"])


@pytest.mark.parametrize("S, W, kept", [(512, 128, "out,lse"),
                                        (512, 1024, "out,lse"),
                                        (256, 128, "")])
def test_attn_lower_says_what_a_site_keeps_through_its_layers_recomputation(
        S, W, kept):
    """A site whose backward is the Pallas kernel (`flash.bwd_plan`'s
    engine, from the shape) keeps its output and logsumexp, sliding or full,
    a window longer than the keys read as none; one on the XLA recompute
    backward (S 256) keeps nothing; `recurrence.lower` counts the values of
    its one-site body."""
    spans = _spans_of_a_step(
        ("attn.lower", "flash.bwd_plan", "recurrence.lower"),
        max_length=S, sliding_window=W)
    sites = spans["attn.lower"]
    assert {s["kind"] for s in sites} == (
        {"sliding", "full"} if W < S else {"full"})
    assert all(s["kept"] == kept and s["kept_bytes"] == (
        4 * S * (16 * 2 + 4) if kept else 0) for s in sites)
    assert {b["engine"] for b in spans["flash.bwd_plan"]} == {
        "pallas" if kept else "xla"}
    assert [r["kept"] for r in spans["recurrence.lower"]] == 4 * [
        2 if kept else 0]


def test_the_residual_writers_start_scaled_by_the_published_depth():
    fluid.reset_default_env()
    spec = models.windowed_decoder(models.WindowedDecoderConfig(
        **{**TINY, "d_model": 64, "d_expert": 48, "vocab_size": 256}))
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    scope = fluid.global_scope()
    std = {p.name: float(np.std(np.asarray(scope.find_var(p.name))))
           for p in fluid.default_main_program().all_parameters()}
    scaled = 0.02 / np.sqrt(2 * 28)
    for name, value in std.items():
        if name.endswith(("_attn_o_w", "_experts_down_w")):
            assert value == pytest.approx(scaled, rel=0.08), name
        elif name.endswith("_w") or name == "embed":
            assert value == pytest.approx(0.02, rel=0.08), name
    assert spec.extras["config"].n_layer == 4
