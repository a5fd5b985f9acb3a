"""The operation counts against numbers worked by hand for one layer at
its published widths."""
from bench.counts import transformer

INTERNLM2_ONE_LAYER = {"hidden_size": 2048, "num_attention_heads": 16,
                       "num_key_value_heads": 8, "intermediate_size": 8192,
                       "vocab_size": 92544, "num_hidden_layers": 1}


def test_transformer_matmul_params():
    # q 2048x2048, k and v 2048x1024 each, o 2048x2048, three of
    # 2048x8192; then the output head 92544x2048
    layer = 4_194_304 + 2 * 2_097_152 + 4_194_304 + 3 * 16_777_216
    assert layer == 62_914_560
    assert transformer.matmul_params(INTERNLM2_ONE_LAYER) == \
        layer + 189_530_112


def test_transformer_attention_and_steps():
    cfg = INTERNLM2_ONE_LAYER
    # scores and values: 2 x 2 x 16 heads x 128 a key
    assert transformer.attention_ops(cfg, 100) == 819_200
    p = 252_444_672
    # a 3-token prompt: 3 tokens through the layer's 62.9M weights, the
    # head once, attention over 1 + 2 + 3 keys
    assert transformer.prefill_ops(cfg, 3) == \
        2 * 62_914_560 * 3 + 2 * 189_530_112 + 8192 * 6
    # forward 2 x params + attention at mean context (S + 1) / 2, times 3
    assert transformer.train_ops_per_token(cfg, 7) == \
        3 * (2 * p + 8192 * 4)

