"""Flash attention kernel + ring attention (sequence parallelism).

Flash kernel runs in Pallas interpret mode on CPU (real kernel on TPU);
ring attention runs on the 8-device virtual CPU mesh."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention
from paddle_tpu.kernels.flash_attention import _reference_attention
from paddle_tpu.longcontext import ring_attention, sequence_parallel_attention


def _rand_qkv(rng, B=2, H=2, S=64, D=16, Sk=None):
    Sk = Sk or S
    q = rng.standard_normal((B, H, S, D)).astype("float32")
    k = rng.standard_normal((B, H, Sk, D)).astype("float32")
    v = rng.standard_normal((B, H, Sk, D)).astype("float32")
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_flash_interpret_matches_reference():
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, S=80, D=16)  # non-multiple of block => padding
    want = _reference_attention(q, k, v, False, 1 / math.sqrt(16))
    got = flash_attention(q, k, v, force="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_interpret_causal():
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, S=64, D=8)
    want = _reference_attention(q, k, v, True, 1 / math.sqrt(8))
    got = flash_attention(q, k, v, causal=True, force="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_grads_flow():
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, S=32, D=8)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, force="jax") ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(
            _reference_attention(q, k, v, True, 1 / math.sqrt(8)) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(3)
    B, H, S, D = 2, 2, 32, 8  # S sharded 4-way -> 8 tokens/device
    q, k, v = _rand_qkv(rng, B=B, H=H, S=S, D=D)

    want = _reference_attention(q, k, v, causal, 1 / math.sqrt(D))
    with mesh:
        got = sequence_parallel_attention(
            mesh, q, k, v, axis="sp", causal=causal, batch_axis=None
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )


def test_ring_attention_with_dp_axis():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, axis_names=("dp", "sp"))
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, B=4, H=2, S=16, D=8)
    want = _reference_attention(q, k, v, True, 1 / math.sqrt(8))
    with mesh:
        got = sequence_parallel_attention(
            mesh, q, k, v, axis="sp", causal=True, batch_axis="dp"
        )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_attention_grads():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(5)
    q, k, v = _rand_qkv(rng, B=1, H=1, S=16, D=4)
    spec = P(None, None, "sp", None)

    def loss(q, k, v):
        with mesh:
            out = shard_map(
                lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss))(q, k, v)
    ref = jax.grad(
        lambda q: jnp.sum(
            _reference_attention(q, k, v, True, 1 / math.sqrt(4)) ** 2
        )
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), atol=1e-4)


def test_transformer_flash_matches_unfused():
    """Flash-attention transformer must produce ~the same loss as the
    bias-tensor formulation (dropout off, same params by construction)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    def build(flash):
        from paddle_tpu.core import framework, scope as scope_mod

        framework.switch_main_program(fluid.Program())
        framework.switch_startup_program(fluid.Program())
        scope_mod._current_scope = scope_mod.Scope()
        cfg = models.TransformerConfig(
            src_vocab_size=64, trg_vocab_size=64, max_length=16,
            n_layer=1, n_head=2, d_model=16, d_inner=32, dropout=0.0,
            use_flash_attention=flash,
        )
        spec = models.transformer(cfg)
        exe = fluid.Executor(fluid.CPUPlace())
        fluid.default_startup_program().random_seed = 7
        exe.run(fluid.default_startup_program())
        batch = spec.synthetic_batch(4)
        (lv,) = exe.run(feed=batch, fetch_list=[spec.loss])
        return float(np.ravel(np.asarray(lv))[0])

    base = build(False)
    flash = build(True)
    assert abs(base - flash) / abs(base) < 1e-3


def test_flash_causal_cross_length():
    # Sq != Sk (cached-decode shape): bottom-right-aligned causal mask must
    # match the reference in kernel (interpret) mode
    rng = np.random.default_rng(6)
    q, k, v = _rand_qkv(rng, B=1, H=1, S=4, D=8, Sk=12)
    want = _reference_attention(q, k, v, True, 1 / math.sqrt(8))
    got = flash_attention(q, k, v, causal=True, force="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("force", ["jax", "interpret"])
def test_flash_empty_sequence_is_zero(force):
    """Both backends must agree: a zero-length row attends to nothing and
    outputs zeros (the pallas kernel's running-max floor guards this — an
    m floor of NEG_INF would make masked p = exp(0) = 1 and average V)."""
    rng = np.random.default_rng(7)
    q, k, v = _rand_qkv(rng, B=2, H=1, S=8, D=4)
    out = flash_attention(q, k, v, k_lengths=jnp.asarray([0, 8]), force=force)
    np.testing.assert_allclose(np.asarray(out)[0], 0.0)
    assert np.abs(np.asarray(out)[1]).sum() > 0


# -- pallas backward kernels (round 3: dq/dkv kernels replace the dense
#    recompute backward) -------------------------------------------------

def _grad_pair(q, k, v, causal=False, k_lengths=None, Dh=None):
    """(pallas-interpret grads, jax-reference grads) for sum(out * w)."""
    Dh = Dh or q.shape[-1]
    scale = 1.0 / math.sqrt(Dh)
    w = jnp.asarray(
        np.random.default_rng(99).standard_normal(q.shape[:3] + (q.shape[-1],))
        .astype("float32"))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, k_lengths=k_lengths,
                              force="interpret")
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        kl = (jnp.asarray(k_lengths, jnp.int32)
              if k_lengths is not None else None)
        out = _reference_attention(q, k, v, causal, scale, k_lengths=kl)
        return jnp.sum(out * w)

    return jax.grad(loss_flash, (0, 1, 2))(q, k, v), \
        jax.grad(loss_ref, (0, 1, 2))(q, k, v)


def _assert_grads_close(got, want, atol=2e-4):
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=atol,
            err_msg=f"d{name} mismatch")


def test_flash_bwd_matches_reference():
    rng = np.random.default_rng(5)
    q, k, v = _rand_qkv(rng, S=64, D=16)
    _assert_grads_close(*_grad_pair(q, k, v))


def test_flash_bwd_causal_padded_seq():
    rng = np.random.default_rng(6)
    # S=80 is not a block multiple: exercises padded q rows (zero dO) and
    # padded k columns in the backward kernels
    q, k, v = _rand_qkv(rng, S=80, D=16)
    _assert_grads_close(*_grad_pair(q, k, v, causal=True))


def test_flash_bwd_key_padding():
    rng = np.random.default_rng(7)
    q, k, v = _rand_qkv(rng, B=3, S=64, D=8)
    lens = np.array([64, 17, 1], np.int32)
    got, want = _grad_pair(q, k, v, k_lengths=lens)
    _assert_grads_close(got, want)
    # keys past each row's length must receive exactly zero grad
    for b, n in enumerate(lens):
        if n < q.shape[2]:
            assert np.abs(np.asarray(got[1])[b, :, n:]).max() == 0
            assert np.abs(np.asarray(got[2])[b, :, n:]).max() == 0


def test_flash_bwd_cross_attention_lengths():
    rng = np.random.default_rng(8)
    q, k, v = _rand_qkv(rng, S=32, Sk=96, D=16)
    _assert_grads_close(*_grad_pair(q, k, v, causal=True))


def test_flash_bwd_bf16_inputs():
    rng = np.random.default_rng(9)
    q, k, v = _rand_qkv(rng, S=64, D=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got, _ = _grad_pair(qb, kb, vb)
    _, want = _grad_pair(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g, dtype=np.float32), np.asarray(r), atol=0.15,
            rtol=0.1, err_msg=f"d{name} bf16 drift")


# -- zigzag (load-balanced) causal context parallelism --------------------

def test_zigzag_permutation_roundtrip():
    from paddle_tpu.longcontext import zigzag_permutation

    perm, inv = zigzag_permutation(16, 4)
    x = np.arange(16)
    np.testing.assert_array_equal(x[perm][inv], x)
    # device 0 holds chunks 0 and 7, device 3 holds chunks 3 and 4
    np.testing.assert_array_equal(perm[:4], [0, 1, 14, 15])
    np.testing.assert_array_equal(perm[-4:], [6, 7, 8, 9])


def test_zigzag_ring_matches_full_causal():
    from paddle_tpu.longcontext import zigzag_sequence_parallel_attention
    from paddle_tpu.parallel import make_mesh

    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, B=2, H=2, S=32, D=8)
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    got = zigzag_sequence_parallel_attention(mesh, q, k, v, batch_axis=None)
    want = _reference_attention(q, k, v, True, 1 / math.sqrt(8))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_ring_grads():
    from paddle_tpu.longcontext import zigzag_sequence_parallel_attention
    from paddle_tpu.parallel import make_mesh

    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, B=1, H=2, S=16, D=4)
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    w = jnp.asarray(rng.standard_normal(q.shape).astype("float32"))

    def loss_z(q, k, v):
        return jnp.sum(
            zigzag_sequence_parallel_attention(mesh, q, k, v,
                                               batch_axis=None) * w)

    def loss_ref(q, k, v):
        return jnp.sum(
            _reference_attention(q, k, v, True, 1 / math.sqrt(4)) * w)

    gz = jax.jit(jax.grad(loss_z, (0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b, name in zip(gz, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name}")


# -- ulysses (all-to-all) sequence parallelism -------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    from jax.sharding import Mesh

    from paddle_tpu.longcontext import ulysses_sequence_parallel_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(11)
    B, H, S, D = 2, 4, 32, 8  # H=4 divisible by the 4-way sp axis
    q, k, v = _rand_qkv(rng, B=B, H=H, S=S, D=D)

    want = _reference_attention(q, k, v, causal, 1 / math.sqrt(D))
    with mesh:
        got = ulysses_sequence_parallel_attention(
            mesh, q, k, v, axis="sp", causal=causal, batch_axis=None
        )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_attention_with_dp_axis():
    from jax.sharding import Mesh

    from paddle_tpu.longcontext import ulysses_sequence_parallel_attention

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, axis_names=("dp", "sp"))
    rng = np.random.default_rng(12)
    q, k, v = _rand_qkv(rng, B=4, H=4, S=16, D=8)
    want = _reference_attention(q, k, v, True, 1 / math.sqrt(8))
    with mesh:
        got = ulysses_sequence_parallel_attention(
            mesh, q, k, v, axis="sp", causal=True, batch_axis="dp"
        )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_attention_grads():
    from jax.sharding import Mesh

    from paddle_tpu.longcontext import ulysses_sequence_parallel_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(13)
    q, k, v = _rand_qkv(rng, B=1, H=4, S=16, D=4)

    def loss(q, k, v):
        with mesh:
            out = ulysses_sequence_parallel_attention(
                mesh, q, k, v, axis="sp", causal=True, batch_axis=None
            )
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(
            _reference_attention(q, k, v, True, 1 / math.sqrt(4)) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ulysses_attention_head_divisibility_error():
    from jax.sharding import Mesh

    from paddle_tpu.longcontext import ulysses_sequence_parallel_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(14)
    q, k, v = _rand_qkv(rng, B=1, H=3, S=16, D=4)  # 3 heads, 4-way axis
    with pytest.raises(ValueError, match="divisible"):
        ulysses_sequence_parallel_attention(mesh, q, k, v, axis="sp")


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_blockwise_key_blocks(causal):
    """block_k smaller than (and not dividing) the sequence exercises the
    online-softmax block loop and the padded final key block."""
    from jax.sharding import Mesh

    from paddle_tpu.longcontext import ulysses_sequence_parallel_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(14)
    B, H, S, D = 2, 4, 24, 8  # S=24 with block_k=7 -> 4 blocks, 4 pad slots
    q, k, v = _rand_qkv(rng, B=B, H=H, S=S, D=D)

    want = _reference_attention(q, k, v, causal, 1 / math.sqrt(D))
    with mesh:
        got = ulysses_sequence_parallel_attention(
            mesh, q, k, v, axis="sp", causal=causal, batch_axis=None,
            block_k=7,
        )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_blockwise_grads():
    from jax.sharding import Mesh

    from paddle_tpu.longcontext import ulysses_sequence_parallel_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.default_rng(15)
    q, k, v = _rand_qkv(rng, B=1, H=4, S=16, D=4)

    def loss(q, k, v):
        with mesh:
            out = ulysses_sequence_parallel_attention(
                mesh, q, k, v, axis="sp", causal=True, batch_axis=None,
                block_k=5,
            )
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(
            _reference_attention(q, k, v, True, 1 / math.sqrt(4)) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("variant", ["ring", "ulysses", "zigzag",
                                     "zigzag-gradient"])
def test_longcontext_s512_sp8(variant):
    """Beyond-toy shape on the full 8-way sp mesh: S=512 (64 tokens per
    device), causal, each of the three sequence-parallel variants against
    the dense reference, and gradient parity for the zigzag form (the
    load-balanced one the long-context bench uses)."""
    from paddle_tpu.longcontext import (
        ulysses_sequence_parallel_attention,
        zigzag_sequence_parallel_attention,
    )
    from paddle_tpu.parallel import make_mesh

    mesh = make_mesh({"sp": 8})
    B, H, S, D = 1, 8, 512, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))

    def dense(q_, k_, v_):
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * scale
        s_ = jnp.where(mask[None, None], s_, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s_, axis=-1), v_)

    if variant == "zigzag-gradient":
        def loss(attend):
            return lambda q_, k_, v_: jnp.sum(attend(q_, k_, v_) ** 2)

        # the zigzag wrapper permutes internally: global view in and out
        gz = jax.jit(jax.grad(loss(
            lambda q_, k_, v_: zigzag_sequence_parallel_attention(
                mesh, q_, k_, v_, batch_axis=None)), argnums=(0, 1, 2)))(
                    q, k, v)
        gr = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gz, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-4)
        return
    got = {
        "ring": lambda: sequence_parallel_attention(
            mesh, q, k, v, causal=True, batch_axis=None),
        "ulysses": lambda: ulysses_sequence_parallel_attention(
            mesh, q, k, v, causal=True, batch_axis=None),
        "zigzag": lambda: zigzag_sequence_parallel_attention(
            mesh, q, k, v, batch_axis=None),
    }[variant]()
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(q, k, v)),
                               rtol=2e-4, atol=2e-5)
