"""moonlight-16b-a3b-ep8 [lm, retriever tower]: Moonlight-16B-A3B
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json,
``model_type`` deepseek_v3) at its published widths, cut to one chip's
share of an 8-chip expert-parallel deployment.

Published: 27 layers at hidden 2,048, ``first_k_dense_replace`` 1 (layer 0
a dense SwiGLU of width 11,264); latent attention without a query LoRA (16
heads, ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim``
64, ``v_head_dim`` 128, ``rope_theta`` 50,000); 26 expert layers of 64
routed experts of width 1,408 and 2 shared (one SwiGLU of width 2,816),
top-6 of sigmoid scores with a selection-only bias (``noaux_tc``),
``norm_topk_prob``, ``routed_scaling_factor`` 2.446; ``rms_norm_eps`` 1e-5;
vocabulary 163,840.

Cuts, each a chip's share and no width:
  * depth: the dense layer and 4 expert layers (published 1 + 26);
  * experts: experts 0-7 of 64 held in every expert layer, as one of 8
    chips that share each layer by expert and data parallelism; the router
    keeps its 64 outputs and top-6, and the absent experts' part is left out;
  * vocabulary: 20,480 of 163,840 ids (an eighth); no LM head, since the
    retriever mean-pools hidden states.
"""

import dataclasses

import jax.numpy as jnp

from repro.configs.base import ArchSpec, register
from repro.models.lm import LMConfig
from repro.models.mla import MLAConfig
from repro.models.moe import MoEConfig

ARCH_ID = "moonlight-16b-a3b-ep8"
PUBLISHED_EXPERTS = 64
CHIPS_PER_LAYER = 8

MOONLIGHT_EP8 = LMConfig(
    name=ARCH_ID,
    n_layers=5,
    first_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=20480,
    rope_theta=50000.0,
    norm_eps=1e-5,
    head=False,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    moe=MoEConfig(
        n_experts=PUBLISHED_EXPERTS,
        top_k=6,
        d_expert=1408,
        dropless=True,
        normalize_top_k=True,
        routed_scale=2.446,
        d_shared=2 * 1408,
        held=(0, PUBLISHED_EXPERTS // CHIPS_PER_LAYER),
    ),
    dtype=jnp.bfloat16,
    attention_impl="plain",
    remat="full",
)

# the same block at CPU-test widths: 1 dense + 2 expert layers, 16 experts
# of which 4 are held, top-3, two shared experts
MOONLIGHT_TINY = dataclasses.replace(
    MOONLIGHT_EP8,
    name="moonlight-tiny",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=1000,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
    moe=dataclasses.replace(MOONLIGHT_EP8.moe, n_experts=16, top_k=3, d_expert=32,
                            d_shared=64, held=(0, 4)),
    dtype=jnp.float32,
)

for cfg in (MOONLIGHT_EP8, MOONLIGHT_TINY):
    register(
        ArchSpec(
            arch_id=cfg.name,
            family="lm",
            model_cfg=cfg,
            shapes={},
            notes="retriever tower: one shared backbone, mean pooling",
        )
    )
