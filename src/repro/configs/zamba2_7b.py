"""zamba2-7b — hybrid: a Mamba2 backbone with two weight-shared attention
blocks (Zyphra/Zamba2-7B-Instruct config.json; arXiv:2411.15242).

81 layers, d_model 3584, vocab 32000.  Every layer is a Mamba2 layer
(d_inner 7168, 112 heads of 64, d_state 64, 2 groups, conv 4 with bias,
chunk 256).  At the 13 ``hybrid_layer_ids`` one of the two shared blocks,
A and B in turn, reads ``concat(h, token embeddings)`` (7168 wide): full
multi-head attention, 32 heads of 224 with RoPE over the whole head, then
an exact (erf) GELU MLP of 14336 whose gate/up projection adds that
application's own rank-128 adapter; a 3584 x 3584 projection of the same
application maps the block's output onto the Mamba layer's input
(``models/transformer.py``).  The config does not state the head tie;
``Zamba2Config``'s default ties it.
"""
from .base import ModelConfig, register

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


def full() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b", family="hybrid",
        num_layers=81, d_model=3584, num_heads=32, num_kv_heads=32,
        head_dim=224, d_ff=14336, vocab_size=32000, act="gelu_exact",
        ssm_state=64, ssm_head_dim=64, ssm_expand=2, ssm_chunk=256,
        ssm_groups=2, ssm_conv_width=4, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=True, hybrid_layer_ids=HYBRID_LAYER_IDS,
        num_mem_blocks=2, adapter_rank=128)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b-smoke", family="hybrid",
        num_layers=7, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=32, d_ff=128, vocab_size=256, act="gelu_exact",
        ssm_state=16, ssm_head_dim=16, ssm_expand=2, ssm_chunk=8,
        ssm_groups=2, tie_embeddings=True, hybrid_layer_ids=(1, 3, 5),
        num_mem_blocks=2, adapter_rank=8, dtype="float32")


register("zamba2-7b", full, smoke)
