"""The operation and byte counts of chipbench/shapes.py against counts made
by hand at the registry's smoke sizes."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from chipbench import shapes  # noqa: E402


def _config(name):
    return json.loads((HERE / "data" / f"{name}.json").read_text())


def test_ssm_train_flops_by_hand():
    c = _config("mamba2-smoke")      # d 64, d_inner 128, 8 heads of 16
    proj = 2 * 64 * (2 * 128 + 2 * 16 + 8) + 2 * 128 * 64     # 54272
    conv = 2 * 4 * (128 + 2 * 16)                             # 1280
    ssd = 8 * (2 * 8 * 16 + 2 * 8 * 16 + 2 * 16 * 16 + 2 * 16 * 16)
    forward = 3 * (proj + conv + ssd) + 2 * 64 * 256          # 236288
    assert forward == 236288
    assert shapes.ssm_forward_flops_per_token(c) == forward
    assert shapes.train_flops_per_token(c, 64) == 3 * forward


def test_dense_decode_and_prefill_by_hand():
    c = _config("danube-smoke")      # d 64, 4 heads + 2 kv of 16, ff 128
    layer = 64 * (64 + 2 * 32) + 64 * 64 + 3 * 64 * 128       # 36864
    head = 64 * 256
    assert shapes.dense_matmul_params(c) == (layer, head)
    # two active slots attending to 5 and 9 positions
    per_slot = 2 * (2 * layer + head)
    attn = 2 * 4 * 4 * 16                                      # per position
    assert shapes.decode_flops(c, [5, 9]) == 2 * per_slot + attn * 14
    weights = (2 * layer + head) * 4                           # float32
    kv = 2 * 2 * 2 * 16 * 4                                    # K and V
    assert shapes.kv_bytes_per_position(c) == kv
    assert shapes.decode_bytes(c, [5, 9]) == weights + 2 * 64 * 4 + kv * 14
    assert shapes.prefill_flops(c, 10) == (2 * 2 * layer * 10 + 2 * head
                                           + attn * 10 * 11 / 2)


def test_least_seconds_takes_the_slower_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert shapes.least_seconds(1000.0, 50.0, peak) == 10.0
    assert shapes.least_seconds(100.0, 50.0, peak) == 5.0


def test_published_danube_kv_bytes_per_position():
    c = json.loads((HERE.parent / "configs" / "h2o-danube-3-4b.json")
                   .read_text())
    # 24 layers x 8 kv heads x 120 x bf16, K and V
    assert shapes.kv_bytes_per_position(c) == 92160
