"""Kimi-Linear-48B-A3B [arXiv:2510.26692; hf
moonshotai/Kimi-Linear-48B-A3B-Instruct]: 27 layers, 3 Kimi Delta
Attention (KDA) layers to 1 MLA layer (NoPE, no q LoRA), 256 routed top-8
experts and 1 shared expert after one dense layer.

Not in ``configs.ARCH_IDS``: the LM model stack and the ``lm/`` study
namespace do not model KDA layers (``param_count`` and ``flops.account``
count every layer as MLA).  The model is served through the ``serve/``
namespace, whose decode-step byte accounting is ``core/lm_serve.py``."""
from repro.configs.base import ArchConfig, LinearAttnSpec, MLASpec, MoESpec

FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)


def config() -> ArchConfig:
    return ArchConfig(
        name="kimi-linear-48b-a3b", family="hybrid",
        n_layers=27, d_model=2304, n_heads=32, n_kv_heads=32, head_dim=72,
        d_ff=9216, vocab=163840, rope_theta=10000.0,
        mla=MLASpec(q_lora_rank=0, kv_lora_rank=512,
                    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
        moe=MoESpec(n_experts=256, top_k=8, d_expert=1024, n_shared=1,
                    first_dense_layers=1, dense_d_ff=9216),
        linear_attn=LinearAttnSpec(
            kda_layers=tuple(i for i in range(1, 28)
                             if i not in FULL_ATTN_LAYERS),
            full_attn_layers=FULL_ATTN_LAYERS,
            n_heads=32, head_dim=128, conv_kernel=4, gate_rank=128),
    )


def reduced_config() -> ArchConfig:
    # one period of the 3:1 pattern (KDA 1-3, MLA 4), the dense layer
    # first, 8 experts: every stream kind of a decode step at toy widths
    return ArchConfig(
        name="kimi-linear-48b-a3b-smoke", family="hybrid",
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab=256,
        mla=MLASpec(q_lora_rank=0, kv_lora_rank=16,
                    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16),
        moe=MoESpec(n_experts=8, top_k=2, d_expert=32, n_shared=1,
                    first_dense_layers=1, dense_d_ff=128),
        linear_attn=LinearAttnSpec(kda_layers=(1, 2, 3),
                                   full_attn_layers=(4,), n_heads=4,
                                   head_dim=16, conv_kernel=4, gate_rank=16),
    )
