"""Serving-path correctness: prefill + decode_step must reproduce the full
forward logits exactly (per family, including SWA / SSM state caches)."""

import jax
import jax.numpy as jnp
import pytest

from repro.models import (
    decode_step, forward, init_decode_caches, lm_init, prefill,
)
from repro.models.config import ModelConfig
from repro.models.stubs import make_prefix_embeddings


def mk(name, **kw):
    base = dict(name=name, arch_type="dense", num_layers=4, d_model=128,
                num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=128,
                dtype=jnp.float32, remat=False, attn_chunk=16)
    base.update(kw)
    return ModelConfig(**base)


CASES = {
    "dense": mk("dense", qkv_bias=True, qk_norm=True),
    "swa": mk("swa", sliding_window=8),
    "moe": mk("moe", arch_type="moe", block_pattern=("moe",), num_experts=4,
              experts_per_tok=2, moe_d_ff=64),
    "xlstm": mk("xlstm", arch_type="ssm", block_pattern=("mlstm", "slstm"),
                ssm_state=16),
    "zamba": mk("zamba", arch_type="hybrid",
                block_pattern=("mamba", "mamba_shared_attn"), ssm_state=16),
    "audio": mk("audio", arch_type="audio", num_codebooks=4, vocab_size=64),
    "vlm": mk("vlm", arch_type="vlm", frontend="vision", frontend_dim=48,
              num_prefix_tokens=4),
    "unrolled": mk("unrolled", scan_layers=False),
}


@pytest.mark.parametrize("family", list(CASES))
def test_decode_matches_forward(family):
    cfg = CASES[family]
    key = jax.random.PRNGKey(0)
    B, S = 2, 16
    params = lm_init(key, cfg)
    s_text = S - cfg.num_prefix_tokens
    tshape = (B, s_text) + ((cfg.num_codebooks,) if cfg.num_codebooks > 1 else ())
    toks = jax.random.randint(key, tshape, 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.frontend:
        batch["prefix_emb"] = make_prefix_embeddings(key, cfg, B)
    logits_full, _ = forward(params, batch, cfg)

    Sp = s_text - 4
    caches = init_decode_caches(cfg, B, S, dtype=jnp.float32)
    pb = dict(batch)
    pb["tokens"] = toks[:, :Sp]
    lg, caches = prefill(params, pb, caches, cfg)
    off = cfg.num_prefix_tokens
    errs = [float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, off + Sp - 1])))]
    for t in range(Sp, s_text):
        lg, caches = decode_step(params, toks[:, t:t + 1], caches, off + t, cfg)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, off + t]))))
    assert max(errs) < 2e-3, (family, errs)


def test_swa_decode_uses_window():
    """With use_window=True, tokens beyond the stacked receptive field
    (num_layers * window) must not influence the decode logits."""
    cfg = mk("swa", sliding_window=2, num_layers=2)  # receptive field = 4
    key = jax.random.PRNGKey(2)
    params = lm_init(key, cfg)
    B, S = 1, 12
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    toks2 = toks.at[:, 0:1].set((toks[:, 0:1] + 7) % cfg.vocab_size)

    def run(tk):
        caches = init_decode_caches(cfg, B, S, dtype=jnp.float32)
        _, caches = prefill(params, {"tokens": tk[:, :-1]}, caches, cfg,
                            use_window=True)
        lg, _ = decode_step(params, tk[:, -1:], caches, S - 1, cfg,
                            use_window=True)
        return lg

    d = float(jnp.max(jnp.abs(run(toks) - run(toks2))))
    assert d < 1e-4, d
