"""Per-arch smoke: reduced config of the same family, one forward + one
train-grad + prefill/decode on CPU; output shapes + finiteness + decode↔
forward consistency (teacher forcing)."""
import pytest

from repro.configs import all_archs, resolve, cells, SHAPES
from repro.testing.model_smoke import smoke_arch


@pytest.mark.parametrize("arch", all_archs())
def test_arch_smoke(arch):
    info = smoke_arch(arch)
    assert info["params"] > 0


def test_ten_archs_assigned():
    assert len(all_archs()) == 10


@pytest.mark.parametrize("arch", all_archs())
def test_cells_assignment(arch):
    cfg = resolve(arch)
    cs = cells(arch)
    assert {"train_4k", "prefill_32k", "decode_32k"} <= set(cs)
    if cfg.subquadratic:
        assert "long_500k" in cs
    else:
        assert "long_500k" not in cs


def test_exact_published_configs():
    q = resolve("qwen1.5-110b")
    assert (q.num_layers, q.d_model, q.num_heads, q.num_kv_heads,
            q.d_ff, q.vocab_size) == (80, 8192, 64, 8, 49152, 152064)
    assert q.qkv_bias
    d = resolve("dbrx-132b")
    assert (d.num_experts, d.experts_per_token) == (16, 4)
    g = resolve("granite-moe-3b-a800m")
    assert (g.num_experts, g.experts_per_token) == (40, 8)
    m = resolve("mamba2-780m")
    assert (m.ssm_state, m.num_layers, m.d_model) == (128, 48, 1536)
    z = resolve("zamba2-7b")
    assert z.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59,
                                  65, 71, 77)
    assert (z.num_layers, z.num_mem_blocks, z.adapter_rank, z.hd(),
            z.ssm_state, z.ssm_groups, z.ssm_chunk) == (81, 2, 128, 224,
                                                        64, 2, 256)
    gr = resolve("granite-34b")
    assert (gr.num_kv_heads, gr.num_layers) == (1, 88)
    w = resolve("whisper-large-v3")
    assert (w.encoder_layers, w.encoder_seq, w.vocab_size) == (32, 1500, 51866)
    h = resolve("h2o-danube-3-4b")
    assert h.sliding_window > 0 and h.d_model == 3840
    lv = resolve("llava-next-mistral-7b")
    assert lv.vision_tokens == 576
    ll = resolve("llama3.2-3b")
    assert ll.tie_embeddings and ll.vocab_size == 128256
