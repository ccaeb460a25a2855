"""Each configuration's ``flops_per_token`` against a count made by hand at
a reduced size."""
from bench import spec

BENCH = {"configs": [
    {"name": "phi3_mini", "file": "bench/configs/phi3_mini.json"},
    {"name": "mamba2_780m", "file": "bench/configs/mamba2_780m.json"}]}


def test_phi3_hand_count():
    mod = spec.config_module(BENCH, "phi3_mini")
    m = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512}
    # weights: q, k, v, o 4 x 64*64, gate, up, down 3 x 64*128 per layer;
    # unembedding 64*512: (2 * 40960 + 32768) * 6 = 688128.
    # attention per token and layer: QK^T and AV, 2 * 2 * 32 keys * 64
    # = 8192 forward; 2 layers, times 3 for the backward: 49152.
    assert mod.flops_per_token(m, 64) == 688128 + 49152


def test_phi3_published_order_of_magnitude():
    mod = spec.config_module(BENCH, "phi3_mini")
    m = spec.config(BENCH, "phi3_mini")["model"]
    # 6 x 211,746,816 weights (113,246,208 in the layer, 98,500,608 in the
    # unembedding) + 6 x 4096 x 3072 of attention in the one layer
    assert mod.flops_per_token(m, 4096) == 6 * 211_746_816 + 75_497_472


def test_mamba2_hand_count():
    mod = spec.config_module(BENCH, "mamba2_780m")
    m = {"n_layers": 2, "d_model": 64, "vocab_size": 512,
         "ssm": {"d_state": 16, "head_dim": 16, "expand": 2, "d_conv": 4,
                 "chunk": 16}}
    # d_inner 128, 8 heads. Weights per layer: in 64 * (256 + 32 + 8),
    # conv 4 * 160, out 128 * 64 = 27776; tied unembedding 64 * 512:
    # (2 * 27776 + 32768) * 6 = 529920. Scan per token and layer: C B^T
    # over half a chunk 16 * 16, the intra-chunk product 16 * 16 * 8, state
    # write and read 4 * 16 * 16 * 8 = 10496; 2 layers, times 3: 62976.
    assert mod.flops_per_token(m, 64) == 529920 + 62976
