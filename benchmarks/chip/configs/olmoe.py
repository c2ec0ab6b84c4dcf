"""Plain reference of the OLMoE decoder, for the benchmark's check.

Written from the published description (Hugging Face ``OlmoeForCausalLM``,
arXiv:2409.02060), in straightforward ``jax.numpy`` and float32, with no
kernel, cache, scan, dispatch or batching across workers.  It imports
nothing of the program under test and makes its own weights from a key.

One layer, on ``x [S, d]``, holding experts ``off .. off + held - 1`` of
the router's ``E`` (``published.num_experts``; ``held`` is the file's
``num_experts``, ``off`` its ``expert_offset``):

    h = rmsnorm(x) * ln1
    q = rmsnorm(h @ wq) * q_norm          (over all H * hd columns at once)
    k = rmsnorm(h @ wk) * k_norm          (over all K * hd columns)
    v = h @ wv
    q, k = rope(q), rope(k)               (per head, rotate-half, rope_theta)
    x = x + causal_softmax(q k^T / sqrt(hd)) v @ wo   (GQA)
    h = rmsnorm(x) * ln2
    p = softmax(h @ router)               ([S, E], float32)
    g, i = top_k(p, k)                    (g / sum(g) if norm_topk_prob)
    x = x + sum over held e of
            (sum_j g_j [i_j == off + e]) * (silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e]

so a token whose top-k picks no held expert gets nothing from the experts,
as on the chip that holds this share; then ``rmsnorm(x) * ln_f`` and the
untied head ``x @ head^T``.

The objective is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times the load-balancing loss, defined as Hugging
Face's ``load_balancing_loss_func`` defines it over the concatenation of
every layer's router probabilities ``p`` (``N = L * S`` rows):

    aux = E * sum_e f_e * P_e,   f_e = #(rows, slots) picking e / N,
                                 P_e = sum of p[:, e] over the rows / N

(f carries no gradient).  ``loss`` returns the cross-entropy, the number
the program reports as its step loss; its gradient is that of the whole
objective: the aux term enters as ``aux - stop_gradient(aux)``, which adds
nothing to the value.

Weights: every matrix, the embedding and the head ~ N(0,
initializer_range), norm scales 1.

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
        "E": cfg["published"]["num_experts"], "held": cfg["num_experts"],
        "off": cfg.get("expert_offset", 0), "k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "aux_coef": cfg["router_aux_loss_coef"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "std": cfg["initializer_range"],
    }


def layer_shapes(cfg: dict) -> dict:
    """Name -> shape of one decoder layer's weights."""
    m = dims(cfg)
    d, H, K, hd, f = m["d"], m["H"], m["K"], m["hd"], m["f"]
    return {"ln1": (d,), "wq": (d, H * hd), "wk": (d, K * hd),
            "wv": (d, K * hd), "wo": (H * hd, d), "q_norm": (H * hd,),
            "k_norm": (K * hd,), "ln2": (d,), "router": (d, m["E"]),
            "w_gate": (m["held"], d, f), "w_up": (m["held"], d, f),
            "w_down": (m["held"], f, d)}


def init(key, cfg: dict) -> dict:
    """``{"embed": [V, d], "layers": [per-layer dict] * L, "ln_f": [d],
    "head": [V, d]}``."""
    m = dims(cfg)
    shapes = layer_shapes(cfg)
    k_emb, k_head, *k_layers = jax.random.split(key, 2 + m["L"])
    layers = []
    for kl in k_layers:
        keys = dict(zip(sorted(shapes), jax.random.split(kl, len(shapes))))
        w = {}
        for name, shp in shapes.items():
            if name.startswith(("ln", "q_norm", "k_norm")):
                w[name] = jnp.ones(shp, jnp.float32)
            else:
                w[name] = m["std"] * jax.random.normal(keys[name], shp,
                                                       jnp.float32)
        layers.append(w)
    V, d = m["V"], m["d"]
    return {"embed": m["std"] * jax.random.normal(k_emb, (V, d), jnp.float32),
            "layers": layers, "ln_f": jnp.ones((d,), jnp.float32),
            "head": m["std"] * jax.random.normal(k_head, (V, d), jnp.float32)}


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
    """One layer; returns the new ``x`` and its router probabilities and
    top-k choices."""
    S = x.shape[0]
    H, K, hd = m["H"], m["K"], m["hd"]
    mm = functools.partial(_mm, precision=precision)
    h = _rmsnorm(x, w["ln1"], m["eps"])
    q = _rmsnorm(mm(h, w["wq"]), w["q_norm"], m["eps"]).reshape(S, H, hd)
    k = _rmsnorm(mm(h, w["wk"]), w["k_norm"], m["eps"]).reshape(S, K, hd)
    v = mm(h, w["wv"]).reshape(S, K, hd)
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    k = jnp.repeat(k, H // K, axis=1).transpose(1, 2, 0)     # [H, hd, S]
    v = jnp.repeat(v, H // K, axis=1).transpose(1, 0, 2)     # [H, S, hd]
    s = mm(q.transpose(1, 0, 2), k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm(p, v).transpose(1, 0, 2).reshape(S, H * hd)
    x = x + mm(o, w["wo"])
    h = _rmsnorm(x, w["ln2"], m["eps"])
    probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)      # [S, E]
    g, top = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    for e in range(m["held"]):
        ge = jnp.sum(jnp.where(top == m["off"] + e, g, 0.0), axis=-1)
        y = mm(jax.nn.silu(mm(h, w["w_gate"][e])) * mm(h, w["w_up"][e]),
               w["w_down"][e])
        x = x + ge[:, None] * y
    return x, probs, top


def parts(weights, tokens, labels, cfg: dict, precision: str = "f32",
          keep=None):
    """``(cross-entropy, aux)`` of one sequence (``tokens``, ``labels``:
    ``[S]`` int): the mean over the positions ``keep`` (``[S]`` bool)
    marks, all where None; ``aux`` is the load-balancing loss times
    ``router_aux_loss_coef``, over every position."""
    m = dims(cfg)
    x = weights["embed"][tokens]
    count = jnp.zeros((m["E"],), jnp.float32)
    prob = jnp.zeros((m["E"],), jnp.float32)
    for w in weights["layers"]:
        x, probs, top = _layer(w, x, m, precision)
        count = count + jnp.sum(jax.nn.one_hot(top, m["E"]), axis=(0, 1))
        prob = prob + jnp.sum(probs, axis=0)
    n = m["L"] * tokens.shape[0]
    aux = m["aux_coef"] * m["E"] * jnp.sum(
        jax.lax.stop_gradient(count) / n * prob / n)
    x = _rmsnorm(x, weights["ln_f"], m["eps"])
    logits = _mm(x, weights["head"].T, precision)
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    if keep is None:
        return jnp.mean(nll), aux
    keep = keep.astype(jnp.float32)
    return jnp.sum(nll * keep) / jnp.sum(keep), aux


def loss(weights, tokens, labels, cfg: dict, precision: str = "f32",
         keep=None):
    """The cross-entropy of one sequence, with the gradient of the whole
    objective (see the module's docstring)."""
    ce, aux = parts(weights, tokens, labels, cfg, precision, keep)
    return ce + (aux - jax.lax.stop_gradient(aux))
