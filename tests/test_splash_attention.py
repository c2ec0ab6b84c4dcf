"""The training attention's TPU path (``kernels/splash_attention.py``) and
the rule that picks it (``models/attention.py``).

* The kernel, in interpret mode, against ``attention_ref``: the output and
  the gradients of q, k and v, vmapped over workers with the layer under
  ``jax.checkpoint``, as the round step runs it; MHA at hd 128 (OLMoE's
  shape class), GQA with groups of 2 at hd 128 and of 7 at hd 64 (Qwen's).
* The path rule as a pure function: only the TPU, with no cache, no
  window, no mesh of more than one device and S a multiple of 128, takes
  the kernel; ``attention_apply`` reads the mesh off its shard hook.
"""

import types

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.splash_attention import block_size, causal_attention
from repro.models.attention import (
    AttnConfig, attention_apply, attention_init, attention_ref,
    uses_flash_kernel,
)

W = 2      # workers, vmapped
S = 256


@pytest.mark.parametrize("heads,kv_heads,hd", [(4, 4, 128), (4, 2, 128),
                                               (7, 1, 64)])
def test_kernel_matches_reference_with_gradients(heads, kv_heads, hd):
    ks = jax.random.split(jax.random.PRNGKey(heads * hd + kv_heads), 4)
    q = jax.random.normal(ks[0], (W, 1, S, heads, hd))
    k = jax.random.normal(ks[1], (W, 1, S, kv_heads, hd))
    v = jax.random.normal(ks[2], (W, 1, S, kv_heads, hd))
    cot = jax.random.normal(ks[3], (W, 1, S, heads, hd))

    def kernel(q, k, v):
        return causal_attention(q, k, v, interpret=True)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            jax.vmap(jax.checkpoint(attn))(q, k, v) * cot)

    out = jax.vmap(kernel)(q, k, v)
    ref = jax.vmap(attention_ref)(q, k, v)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    grads = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_ref), argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", grads, want):
        err = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
        assert err < 1e-5, (name, err)


def test_block_size_divides_the_sequence():
    assert [block_size(s) for s in (128, 384, 512, 1024, 1280, 4096)] == \
        [128, 128, 512, 512, 256, 512]


BASE = dict(backend="tpu", cached=False, window=None, sharded=False,
            seq_len=4096)


@pytest.mark.parametrize("change,want", [
    ({}, True),
    ({"seq_len": 1024}, True),
    ({"seq_len": 128}, True),
    ({"backend": "cpu"}, False),
    ({"backend": "gpu"}, False),
    ({"cached": True}, False),
    ({"window": 256}, False),
    ({"sharded": True}, False),
    ({"seq_len": 1000}, False),
    ({"seq_len": 16}, False),
])
def test_path_rule(change, want):
    assert uses_flash_kernel(**{**BASE, **change}) is want


def _kernel_calls(monkeypatch, backend, shard=None, seq_len=128,
                  window=None) -> int:
    """Kernel calls in one attention block lowered for the TPU, with the
    backend ``jax.default_backend`` reports steered."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = AttnConfig(d_model=64, num_heads=2, num_kv_heads=1, head_dim=128,
                     sliding_window=window)
    p = attention_init(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, seq_len, 64), jnp.bfloat16)
    pos = jnp.arange(seq_len)[None]
    kw = {} if shard is None else {"shard": shard}

    def f(p, x):
        return attention_apply(p, x, pos, cfg, use_window=True, **kw)[0]

    text = jax.jit(f).trace(p, x).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count("tpu_custom_call")


def _hook(size):
    hook = lambda x, name: x  # noqa: E731
    hook.mesh = types.SimpleNamespace(size=size)
    return hook


def test_attention_apply_follows_the_rule(monkeypatch):
    assert _kernel_calls(monkeypatch, "tpu") == 1
    assert _kernel_calls(monkeypatch, "tpu", shard=_hook(1)) == 1
    assert _kernel_calls(monkeypatch, "tpu", shard=_hook(4)) == 0
    assert _kernel_calls(monkeypatch, "tpu", window=64) == 0
    assert _kernel_calls(monkeypatch, "tpu", seq_len=96) == 0
    assert _kernel_calls(monkeypatch, "cpu") == 0


def test_shard_hook_names_its_mesh():
    from repro.launch.mesh import make_mesh
    from repro.sharding import make_shard_hook
    assert getattr(make_shard_hook(None), "mesh", None) is None
    mesh = make_mesh((1, 1), ("data", "model"))
    assert make_shard_hook(mesh).mesh is mesh
