"""olmoe-1b-7b [moe] — 16L d_model=2048 16H (kv=16) vocab=50304,
MoE 64 experts top-8 (gates not renormalised), expert d_ff=1024 — q/k norm
over the whole projection.  [arXiv:2409.02060]

Every layer is sparse and holds all 64 experts (``experts_held`` cuts that
to one chip's share, as the chip benchmark's ``olmoe-1b-7b`` file does).
On a mesh the stacked expert weights shard their expert dim over the
``model`` axis (``sharding/specs.py``), and GSPMD places the dispatch's
gathers; there is no explicit all-to-all.  ``sliding_window`` is the
published context (4096): training at up to 4096 positions is unchanged by
it, and it lets the dry-run lower ``long_500k`` decode against a
4096-position window, which the published model does not have.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b",
    arch_type="moe",
    num_layers=16,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1024,
    moe_d_ff=1024,
    vocab_size=50304,
    head_dim=128,
    qk_norm="full",
    block_pattern=("moe",),
    num_experts=64,
    experts_per_tok=8,
    norm_topk_prob=False,
    router_aux_loss_coef=0.01,
    sliding_window=4096,
    n_workers=16,
    source="arXiv:2409.02060",
)
