"""Plain reference of resnet50: forward in training mode (batch statistics),
loss and gradient in fp32 jnp, written from He et al. 2015 (stride on the 3x3
of a bottleneck) and the order in which paddle_tpu/models/resnet.py makes its
parameters, and from nothing else of the program (no op, no layout or AMP
tier, no flag).  Parameters come in under the program's names
(`conv2d_<i>.w_0`, `batch_norm_<i>.w_0/.b_0`, `fc_0.w_0/.b_0`: the i-th
convolution is followed by the i-th batch norm), so the gradient goes out
under them too."""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def _conv_bn(p, i, x, stride, pad, relu, eps=1e-5):
    y = jax.lax.conv_general_dilated(
        x, p[f"conv2d_{i}.w_0"], (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=(0, 2, 3), keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + eps)
         * p[f"batch_norm_{i}.w_0"][None, :, None, None]
         + p[f"batch_norm_{i}.b_0"][None, :, None, None])
    return jax.nn.relu(y) if relu else y


def _bottleneck(p, i, x, ch, stride, project):
    """The block whose first parameter index is i: the projection of the
    shortcut where the block has one, then 1x1, 3x3, 1x1."""
    short = x
    if project:
        short = _conv_bn(p, i, x, stride, 0, relu=False)
        i += 1
    y = _conv_bn(p, i, x, 1, 0, relu=True)
    y = _conv_bn(p, i + 1, y, stride, 1, relu=True)
    y = _conv_bn(p, i + 2, y, 1, 0, relu=False)
    return jax.nn.relu(short + y)


def _loss(p, batch, cfg, feed_names):
    img, label = (batch[n] for n in feed_names)
    x = _conv_bn(p, 0, img.astype(jnp.float32), 2, 3, relu=True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)))
    i = 1
    for stage, count in enumerate(STAGES[cfg["depth"]]):
        for block in range(count):
            stride = 2 if stage > 0 and block == 0 else 1
            # the block is recomputed in the backward pass: at 224x224 and
            # batch 256 its fp32 activations would not fit beside the rest
            ch = 64 * 2 ** stage
            project = x.shape[1] != ch * 4 or stride != 1
            x = jax.checkpoint(_bottleneck, static_argnums=(1, 3, 4, 5))(
                p, i, x, ch, stride, project)
            i += 4 if project else 3
    x = jnp.mean(x, axis=(2, 3))
    logits = jnp.matmul(x, p["fc_0.w_0"], precision=HIGHEST) + p["fc_0.b_0"]
    prob = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(prob, label.reshape(-1, 1), axis=-1)
    return jnp.mean(-jnp.log(picked + 1e-12))


def loss_and_grad(params, batch, cfg, feed_names, trainable, micro):
    """(loss, {name: gradient}).  Batch norm takes its statistics over the
    whole batch, so the batch is taken whole and `micro` is not used."""
    del micro
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    fixed = {k: v for k, v in params.items() if k not in trainable}
    free = {k: v for k, v in params.items() if k in trainable}
    return jax.value_and_grad(
        lambda free: _loss({**fixed, **free}, batch, cfg, feed_names))(free)
