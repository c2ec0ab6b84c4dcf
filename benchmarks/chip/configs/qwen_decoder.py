"""Plain reference of the Qwen2 / Qwen3 decoder, for the benchmark's check.

Written from the published description (Hugging Face ``Qwen2ForCausalLM`` /
``Qwen3ForCausalLM``), in straightforward ``jax.numpy`` and float32, with
no kernel, cache, scan or batching across workers.  It imports nothing of
the program under test and makes its own weights from a key.

One layer, on ``x [S, d]``:

    h = rmsnorm(x) * ln1
    q, k, v = h @ wq (+ bq), h @ wk (+ bk), h @ wv (+ bv)   (bias: Qwen2)
    q, k = rmsnorm_head(q) * q_norm, rmsnorm_head(k) * k_norm  (Qwen3)
    q, k = rope(q), rope(k)               (rotate-half, base rope_theta)
    x = x + causal_softmax(q k^T / sqrt(head_dim)) v @ wo   (GQA)
    h = rmsnorm(x) * ln2
    x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down

then ``rmsnorm(x) * ln_f`` and the tied head ``x @ embed^T``; the loss is
the mean next-token cross-entropy over all positions.

Weights: every matrix and the embedding ~ N(0, initializer_range), biases
0, norm scales 1 (the published initialisation of these models).

``precision`` selects how every matrix product runs: ``"f32"`` at full
float32 precision (the reference), or ``"fp8"``: both operands quantised
per tensor to float8 (e4m3 forward, e5m2 for the cotangents of the
backward pass), accumulated in float32.  ``"fp8"`` is the benchmark's
control: one precision step below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from the published config keys."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "d": d, "L": cfg["num_hidden_layers"], "H": H,
        "K": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // H,
        "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "bias": bool(cfg.get("attention_bias",
                             cfg["model_type"] == "qwen2")),
        "qk_norm": cfg["model_type"] == "qwen3",
        "std": cfg["initializer_range"],
    }


def layer_shapes(cfg: dict) -> dict:
    """Name -> shape of one decoder layer's weights."""
    m = dims(cfg)
    d, H, K, hd, f = m["d"], m["H"], m["K"], m["hd"], m["f"]
    out = {"ln1": (d,), "wq": (d, H * hd), "wk": (d, K * hd),
           "wv": (d, K * hd), "wo": (H * hd, d), "ln2": (d,),
           "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if m["bias"]:
        out.update(bq=(H * hd,), bk=(K * hd,), bv=(K * hd,))
    if m["qk_norm"]:
        out.update(q_norm=(hd,), k_norm=(hd,))
    return out


def init(key, cfg: dict) -> dict:
    """``{"embed": [V, d], "layers": [per-layer dict] * L, "ln_f": [d]}``."""
    m = dims(cfg)
    shapes = layer_shapes(cfg)
    k_emb, *k_layers = jax.random.split(key, 1 + m["L"])
    layers = []
    for kl in k_layers:
        keys = dict(zip(sorted(shapes), jax.random.split(kl, len(shapes))))
        w = {}
        for name, shp in shapes.items():
            if name.startswith(("ln", "q_norm", "k_norm")):
                w[name] = jnp.ones(shp, jnp.float32)
            elif name.startswith("b"):
                w[name] = jnp.zeros(shp, jnp.float32)
            else:
                w[name] = m["std"] * jax.random.normal(keys[name], shp,
                                                       jnp.float32)
        layers.append(w)
    embed = m["std"] * jax.random.normal(k_emb, (m["V"], m["d"]), jnp.float32)
    return {"embed": embed, "layers": layers,
            "ln_f": jnp.ones((m["d"],), jnp.float32)}


# ------------------------------------------------------------ matmuls

def _q8(x, dtype):
    """Per-tensor scaled round trip through a float8 type."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _mm_fp8(a, b):
    return jnp.matmul(_q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn),
                      precision=HIGHEST)


def _mm_fp8_fwd(a, b):
    qa, qb = _q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _mm_fp8_bwd(res, g):
    qa, qb = res
    qg = _q8(g, jnp.float8_e5m2)
    return (jnp.matmul(qg, jnp.swapaxes(qb, -1, -2), precision=HIGHEST),
            jnp.matmul(jnp.swapaxes(qa, -1, -2), qg, precision=HIGHEST))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(a, b, precision: str):
    if precision == "fp8":
        return _mm_fp8(a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


# ------------------------------------------------------------ the model

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: [S, heads, hd]; rotate-half RoPE at positions 0..S-1."""
    S, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(w, x, m, precision):
    S = x.shape[0]
    H, K, hd = m["H"], m["K"], m["hd"]
    mm = functools.partial(_mm, precision=precision)
    h = _rmsnorm(x, w["ln1"], m["eps"])
    q, k, v = mm(h, w["wq"]), mm(h, w["wk"]), mm(h, w["wv"])
    if m["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = (q.reshape(S, H, hd), k.reshape(S, K, hd),
               v.reshape(S, K, hd))
    if m["qk_norm"]:
        q = _rmsnorm(q, w["q_norm"], m["eps"])
        k = _rmsnorm(k, w["k_norm"], m["eps"])
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    k = jnp.repeat(k, H // K, axis=1).transpose(1, 2, 0)     # [H, hd, S]
    v = jnp.repeat(v, H // K, axis=1).transpose(1, 0, 2)     # [H, S, hd]
    s = mm(q.transpose(1, 0, 2), k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm(p, v).transpose(1, 0, 2).reshape(S, H * hd)
    x = x + mm(o, w["wo"])
    h = _rmsnorm(x, w["ln2"], m["eps"])
    return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                  w["w_down"])


def loss(weights, tokens, labels, cfg: dict, precision: str = "f32",
         keep=None):
    """Mean next-token cross-entropy of one sequence (``tokens``,
    ``labels``: ``[S]`` int).  ``keep`` (``[S]`` bool) restricts the mean to
    the positions it marks."""
    m = dims(cfg)
    x = weights["embed"][tokens]
    for w in weights["layers"]:
        x = _layer(w, x, m, precision)
    x = _rmsnorm(x, weights["ln_f"], m["eps"])
    logits = _mm(x, weights["embed"].T, precision)
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    if keep is None:
        return jnp.mean(nll)
    keep = keep.astype(jnp.float32)
    return jnp.sum(nll * keep) / jnp.sum(keep)
