"""`bench/flops.py` against counts made by hand for one decode call of
each configuration."""
import json
import pathlib

from bench import flops

CONF = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _conf(name):
    with open(CONF / f"{name}.json") as f:
        return json.load(f)


def test_yi_decode_call():
    conf = _conf("yi-9b-24l")
    # d 4096, f 11008, 32 q heads, 4 kv heads, hd 128, 24 layers, V 64000
    per_layer = 4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096 + 3 * 4096 * 11008
    assert per_layer == 173015040
    lora = lambda r: 2 * r * (4096 + 4096) + 2 * 2 * r * (4096 + 512)
    rows = [(300, 8), (1000, 64)]
    want = sum(24 * (2 * per_layer + lora(r) + 4 * 32 * 128 * c)
               + 2 * 4096 * 64000 for c, r in rows)
    assert flops.decode_flops(conf, rows) == want
    kv = 24 * sum(2 * c * 4 * 128 * 2 + 2 * 32 * 128 * 2 for c, _ in rows)
    assert flops.paged_attn_bytes(conf, [300, 1000]) == kv
    assert flops.paged_attn_flops(conf, [300, 1000]) == \
        24 * 4 * 32 * 128 * 1300


def test_phi_decode_call():
    conf = _conf("phi-3-mini-4k")
    # d 3072, f 8192, 32 heads (MHA), hd 96, 32 layers, V 32064
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    lora = lambda r: 3 * 2 * r * (3072 + 3072)
    rows = [(1100, 16)] * 8
    want = 8 * (32 * (2 * per_layer + lora(16) + 4 * 32 * 96 * 1100)
                + 2 * 3072 * 32064)
    assert flops.decode_flops(conf, rows) == want
    # 8 rows at 1100 tokens: K and V of 32 heads x 96 in bf16, per layer
    kv = 32 * 8 * (2 * 1100 * 32 * 96 * 2 + 2 * 32 * 96 * 2)
    assert flops.paged_attn_bytes(conf, [1100] * 8) == kv


def test_prefill_is_causal_and_unembeds_once():
    conf = _conf("yi-9b-24l")
    one = flops.prefill_flops(conf, 1, 8)
    assert one == flops.token_flops(conf, 1, 8)
    # the causal attention term of a 3-token prompt sees 1 + 2 + 3 tokens
    three = flops.prefill_flops(conf, 3, 8)
    assert three == 3 * one - 2 * 2 * 4096 * 64000 \
        + 24 * 4 * 32 * 128 * (1 + 2 + 3 - 3)
