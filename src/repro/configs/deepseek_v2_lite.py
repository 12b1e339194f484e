"""deepseek-v2-lite-16b [moe] — DeepSeek-V2-Lite as its published
config.json gives it (huggingface.co/deepseek-ai/DeepSeek-V2-Lite;
arXiv:2405.04434).

* Multi-head latent attention: 16 heads, no q compression, qk nope/rope
  128/64, v 128, a 512-wide latent with its RMSNorm (kv_a_layernorm)
  before it is cached and decompressed.
* YaRN rope over the 64 rope dims: factor 40 from 4096 positions,
  beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707, so the
  frequencies blend and the softmax scale is 192**-0.5 * mscale**2 with
  mscale = 0.1 * 0.707 * ln 40 + 1.
* One leading dense layer, SwiGLU 10944 (first_k_dense_replace 1), then
  26 expert layers: 64 routed experts of 1408, top-6 softmax gates not
  renormalised (norm_topk_prob false, routed_scaling_factor 1), and 2
  shared experts (one SwiGLU of 2 x 1408).

Rope layout: the repo rotates halves (x[:32], x[32:]); DeepSeek rotates
interleaved pairs.  The two differ by a fixed permutation of the rope
columns of ``wq`` and ``wkv_a``, which random weights cannot tell apart.

``FULL`` holds every expert.  A chip of an expert-parallel deployment
holds a contiguous block of them: ``FULL.with_(moe=dataclasses.replace(
FULL.moe, held=16, first_held=16 * chip))`` on a v5e-4 host.
"""
import dataclasses

from .base import MLAConfig, ModelConfig, MoEConfig, YarnConfig, register

FULL = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,             # MLA: kv heads == q heads post-decompression
    d_ff=10944,                # the leading dense layer's width
    vocab=102400,
    rope_theta=10000.0,
    yarn=YarnConfig(factor=40.0, original_max_positions=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    activation="silu",
    norm_eps=1e-6,
    tie_embeddings=False,
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff=1408,
                  sharding="ep", norm_topk_prob=False, first_dense=1),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite; "
           "arXiv:2405.04434",
)

# One dense and two expert layers; 8 experts of which a layer may hold a
# quarter (the share tests); yarn with a small original window so that
# its ramp falls inside the 8 rope dims.
SMOKE = FULL.with_(
    name="dsv2-smoke", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab=256,
    yarn=YarnConfig(factor=4.0, original_max_positions=64, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=dataclasses.replace(FULL.moe, n_experts=8, top_k=3, n_shared=1,
                            d_ff=32),
    mla=MLAConfig(kv_lora_rank=16, rope_head_dim=8, nope_head_dim=16,
                  v_head_dim=16),
    dtype="float32", param_dtype="float32")

register("deepseek-v2-lite-16b", FULL, SMOKE)
