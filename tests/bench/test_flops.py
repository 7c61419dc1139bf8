"""FLOP and byte counts on worked cases."""
from bench import record
from bench.reference import model as dense


def test_decode_token_flops_granite_shapes():
    s = dense.Sizes(n_layers=8, d_model=4096, n_heads=32, n_kv_heads=8,
                    head_dim=128, d_ff=12800, vocab=49155,
                    vocab_padded=49408, gated=True, embed_stub=False,
                    norm_eps=1e-5, rope_theta=1e4)
    per_layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12800
    assert per_layer == 199_229_440
    weights = 8 * per_layer + 4096 * 49155
    assert dense.decode_token_flops(s, 0) == 2 * weights
    # attention: QK and AV, 2 FLOPs a multiply-add, over 100 positions
    assert dense.decode_token_flops(s, 100) - 2 * weights \
        == 4 * 8 * 32 * 128 * 100


def test_gelu_mlp_has_two_matrices():
    s = dense.Sizes(n_layers=1, d_model=4, n_heads=1, n_kv_heads=1,
                    head_dim=4, d_ff=8, vocab=10, vocab_padded=256,
                    gated=False, embed_stub=True, norm_eps=1e-5,
                    rope_theta=1e4)
    assert dense.decode_token_flops(s, 0) == 2 * (4 * 16 + 2 * 4 * 8 + 40)


def test_token_flops_counts_the_tokens_of_the_given_steps():
    s = dense.Sizes(1, 4, 1, 1, 4, 8, 10, 256, False, True, 1e-5, 1e4)
    r = record.RequestLog(0, prompt_len=3, gen=4, arrival=0.0,
                          token_steps=[-1, 0, 1, 2])
    run = record.Run("w", s, {}, 0.0, (0.0, 1.0), [], [r],
                     flops=dense.decode_token_flops)
    steps = [record.Step("decode", i, 0, 1, False) for i in (1, 2)]
    want = dense.decode_token_flops(s, 5) + dense.decode_token_flops(s, 6)
    assert record.token_flops(run, steps) == want
