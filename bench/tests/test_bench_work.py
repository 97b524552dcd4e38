"""FLOPs and bytes from shapes, against hand counts, through the LLaMA-family
architecture module ``bench/arch/llama.py``: the committed qwen2-7b
configuration (GQA with QKV bias) and deepseek-llm-7b (published widths
cut to 10 layers; full multi-head attention, no bias)."""
import pytest

from bench.arch import llama
from bench.model import arch, load_config

HAND = {
    "qwen2-7b": llama.Dims(layers=14, d_model=3584, n_heads=28, n_kv_heads=4,
                           d_head=128, d_ff=18944, vocab=152064,
                           qkv_bias=True),
    "deepseek-llm-7b": llama.Dims(layers=10, d_model=4096, n_heads=32,
                                  n_kv_heads=32, d_head=128, d_ff=11008,
                                  vocab=102400, qkv_bias=False),
}


def dims(name):
    c = load_config(name)
    assert arch(c) is llama
    return arch(c).counts(c)


@pytest.mark.parametrize("name", sorted(HAND))
def test_from_config_reads_the_published_keys(name):
    assert dims(name) == HAND[name]


def test_qwen2_7b_counts():
    d = dims("qwen2-7b")
    # q 3584x28x128, k/v 3584x4x128, o 28x128x3584, MLP 3 x 3584x18944
    attn = 3584 * 3584 + 2 * 3584 * 512 + 3584 * 3584
    assert d.layer_matmul_params == attn + 3 * 3584 * 18944 == 233_046_016
    assert d.layer_params == 233_046_016 + (28 + 8) * 128 + 2 * 3584
    assert d.kv_bytes_per_token == 2 * 14 * 4 * 128 * 2 == 28_672
    assert d.weight_bytes() == 2 * (14 * d.layer_params
                                    + 2 * 152064 * 3584 + 3584)


def test_deepseek_counts():
    d = dims("deepseek-llm-7b")
    assert d.layer_matmul_params == 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert d.layer_params == d.layer_matmul_params + 2 * 4096   # no bias
    assert d.kv_bytes_per_token == 2 * 10 * 32 * 128 * 2 == 163_840


def test_decode_step_by_hand():
    d = dims("deepseek-llm-7b")
    ctx = [100, 2000]
    f, b = d.decode_step(ctx)
    per_tok = 2 * 10 * d.layer_matmul_params + 2 * 4096 * 102400
    attn = 4 * 10 * 32 * 128 * (100 + 2000)
    assert f == 2 * per_tok + attn
    weights = 2 * (10 * d.layer_params + 4096 * 102400 + 4096 + 2 * 4096)
    kv = 163_840 * (99 + 1999) + 163_840 * 2
    assert b == weights + kv


def test_prefill_chunk_by_hand():
    d = dims("qwen2-7b")
    # positions 64..127 attend over 65..128 keys: sum = 6240
    f = d.prefill_chunk_flops(64, 128, final=False)
    assert f == 64 * 2 * 14 * d.layer_matmul_params \
        + 4 * 14 * 28 * 128 * sum(range(65, 129))
    g = d.prefill_chunk_flops(64, 128, final=True)
    assert g - f == 2 * 3584 * 152064
    assert d.step_flops([], [(64, 128, True)]) == g
