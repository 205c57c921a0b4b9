"""Required work of both configurations against hand counts."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench.flops import dense_lora as fl  # noqa: E402
from bench.models.dense_lora import spec as model_spec  # noqa: E402


def spec(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return model_spec(json.load(f))


# hand counts, per token: base projections forward (2P) and input-gradient
# (2P less layer 0's input projections), LM head forward and input-gradient
# (4 d V), causal attention (6 * 2 * H * hd * (S + 1) / 2 per layer) and
# LoRA (6 r (d_in + d_out) per projection, less 2 r d_in at layer 0's
# input projections)
HAND = {
    # d, H, KV, hd, F, L, V, mlp
    "qwen25-7b-l6": (3584, 28, 4, 128, 18944, 6, 152064, "swiglu"),
    "starcoder2-7b-l8": (4608, 36, 4, 128, 18432, 8, 49152, "gelu2"),
}


def hand_flops_per_token(name, seq, rank):
    d, H, KV, hd, F, L, V, mlp = HAND[name]
    projs = {"q": (d, H * hd), "k": (d, KV * hd), "v": (d, KV * hd),
             "o": (H * hd, d), "up": (d, F), "down": (F, d)}
    if mlp == "swiglu":
        projs["gate"] = (d, F)
    inputs = {"q", "k", "v", "gate", "up"}
    base = sum(2 * a * b * L + 2 * a * b * (L - (n in inputs))
               for n, (a, b) in projs.items())
    head = 4 * d * V
    attn = L * 6 * 2 * H * hd * (seq + 1) / 2
    lora = sum(6 * rank * (a + b) * L - (2 * rank * a if n in inputs else 0)
               for n, (a, b) in projs.items())
    return base + head + attn + lora


@pytest.mark.parametrize("name", sorted(HAND))
@pytest.mark.parametrize("rank", [8, 128])
def test_job_flops_match_hand_count(name, rank):
    s = spec(name)
    got = fl.job_flops(s, 1024, [(2, rank)])
    assert got == pytest.approx(2 * 1024 * hand_flops_per_token(name, 1024, rank),
                                rel=1e-12)


def test_qwen_flops_per_token_value():
    # 2P = 2 * 6 * 233.0M = 2.80G, its input-gradient 2.49G, the LM head
    # 4 * 3584 * 152064 = 2.18G, attention 0.13G, LoRA at rank 8 0.03G
    s = spec("qwen25-7b-l6")
    per_token = fl.job_flops(s, 1024, [(1, 8)]) / 1024
    assert 7.60e9 < per_token < 7.65e9


def test_base_work_is_shared_by_a_pack():
    s = spec("qwen25-7b-l6")
    pack = fl.job_products(s, 1024, [(1, 8), (2, 8)])
    alone = fl.job_products(s, 1024, [(3, 8)])
    # the same tokens: the same flops; the pack reads the base once too
    assert sum(f for _, f, _ in pack) == pytest.approx(
        sum(f for _, f, _ in alone), rel=1e-3)
    base_bytes = [b for n, _, b in pack if n == "up.fwd"]
    t, d, F = 3 * 1024, 3584, 18944
    assert base_bytes == [6 * 2.0 * (t * d + d * F + t * F)]


def test_least_time_bounds():
    s = spec("starcoder2-7b-l8")
    ad = [(1, 16), (2, 16)]
    flops = fl.job_flops(s, 1024, ad)
    least = fl.job_least_seconds(s, 1024, ad, 197e12, 819e9)
    assert least >= flops / 197e12
    assert least < 2 * flops / 197e12
