"""decoder2048: the DecodeConfig, the weights made on the device from the
seed, and the plain reference of benchmark/configs/decoder2048.json."""

import math

import numpy as np

from benchmark.harness.traffic import fold_seed


def decode_config(cfg: dict, max_length: int):
    from paddle_tpu import serving

    assert cfg["d_model"] == cfg["n_head"] * cfg["head_dim"], cfg
    return serving.DecodeConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["d_model"],
        n_head=cfg["n_head"], n_layer=cfg["n_layer"],
        d_inner=cfg["d_inner"], max_length=max_length)


def sinusoid_table(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    dim = np.arange(d_model // 2, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2.0 * dim / d_model)
    table = np.zeros((max_len, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table


def build_params(dcfg, seed: int, device=None):
    """The dictionary serving.init_decode_params makes (same keys, shapes,
    types and 1/sqrt(fan_in) scale), drawn on the device in one jitted call:
    the program's own builder draws every weight with numpy on the host and
    uploads it, which every run of every later check would pay."""
    import jax
    import jax.numpy as jnp

    d, f, V, L = dcfg.d_model, dcfg.d_inner, dcfg.vocab_size, dcfg.n_layer
    d_kv = dcfg.num_kv_heads * dcfg.head_dim
    mats = {"wq": (d, d), "wk": (d, d_kv), "wv": (d, d_kv), "wo": (d, d),
            "w1": (d, f), "w2": (f, d)}

    def make(key):
        keys = jax.random.split(key, L * len(mats) + 1)
        layers, i = [], 0
        for _ in range(L):
            lp = {}
            for name, (n_in, n_out) in mats.items():
                lp[name] = jax.random.normal(
                    keys[i], (n_in, n_out), jnp.float32) / math.sqrt(n_in)
                i += 1
            lp.update(ln1_g=jnp.ones(d), ln1_b=jnp.zeros(d),
                      b1=jnp.zeros(f), b2=jnp.zeros(d),
                      ln2_g=jnp.ones(d), ln2_b=jnp.zeros(d))
            layers.append(lp)
        embed = jax.random.normal(keys[i], (V, d), jnp.float32) / math.sqrt(d)
        return {"embed": embed, "layers": layers}

    key = jax.random.PRNGKey(fold_seed(seed))
    if device is not None:
        key = jax.device_put(key, device)
    params = jax.jit(make)(key)
    params["pos"] = jax.device_put(
        sinusoid_table(dcfg.max_length, d), device)
    return params


def reference_forward(params, dcfg, tokens):
    """The plain reference: whole sequences at once, dense causal attention,
    no cache, no kernel, float32 with matmul precision "highest".
    tokens [N, S] (padded at the end; attention is causal, so padding
    changes no earlier row) -> logits [N, S, V].  Post-norm blocks, sinusoid
    positions added to sqrt(d)-scaled embeddings, ReLU, logits through the
    embedding's transpose (Vaswani 2017, 3.1-3.4, the decoder without
    cross-attention).  Written from the equations, not from
    serving.full_forward.  One layer is one jitted function, called once a
    layer: every layer has the same shapes, so it compiles once."""
    import jax
    import jax.numpy as jnp

    H, Dh = dcfg.n_head, dcfg.head_dim

    def norm(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    @jax.jit
    def layer(x, lp):
        N, S, _ = x.shape
        causal = jnp.tril(jnp.ones((S, S), bool))
        q = (x @ lp["wq"]).reshape(N, S, H, Dh)
        k = (x @ lp["wk"]).reshape(N, S, H, Dh)
        v = (x @ lp["wv"]).reshape(N, S, H, Dh)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        a = jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(N, S, H * Dh)
        x = norm(x + a @ lp["wo"], lp["ln1_g"], lp["ln1_b"])
        u = jnp.maximum(x @ lp["w1"] + lp["b1"], 0.0)
        return norm(x + u @ lp["w2"] + lp["b2"], lp["ln2_g"], lp["ln2_b"])

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens] * math.sqrt(dcfg.d_model) \
            + params["pos"][:tokens.shape[1]]
        for lp in params["layers"]:
            x = layer(x, lp)
        return np.asarray(x @ params["embed"].T)
