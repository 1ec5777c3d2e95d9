"""Operation and byte counts against values worked by hand."""
from bench.counts import flash_attention, model, ssd_scan


def test_ssd_scan_small_shape():
    # Q=2, two chunks; per chunk and head: 2*4*3 + 2*4*2 + 4*2*3*2 = 88
    flops, nbytes = ssd_scan.cost(batch=1, seq_len=4, heads=1, head_dim=2,
                                  state=3, groups=1, chunk=2)
    assert flops == 176
    # x and y 2*2*4*2, dt 4*4, B and C 2*2*4*3, state 4*2*3
    assert nbytes == 32 + 16 + 48 + 24


def test_flash_attention_pairs_and_cost():
    assert flash_attention.pairs(4, 2) == 1 + 2 + 2 + 2
    assert flash_attention.pairs(5, 0) == 15          # plain causal
    assert flash_attention.pairs(3, 8) == 6           # window wider than S
    flops, nbytes = flash_attention.cost(batch=1, seq_len=4, heads=2,
                                         kv_heads=1, head_dim=8, window=2)
    assert flops == 4 * 8 * 2 * 7
    assert nbytes == 2 * 4 * 8 * (2 * 2 + 2 * 1)


MAMBA_SMALL = {"family": "ssm", "d_model": 4, "ssm_expand": 2,
               "ssm_head_dim": 4, "ssm_state": 2, "ssm_ngroups": 1,
               "conv_kernel": 2, "n_layers": 1, "vocab_size": 10,
               "ssm_chunk": 2}


def test_layer_matmuls_by_hand():
    # d_inner 8, 2 heads; in_proj 4 x (16 + 4 + 2), out_proj 8 x 4,
    # conv 2 taps over 8 + 4 channels
    assert model.layer_matmul_flops_per_token(MAMBA_SMALL) == \
        2 * 4 * 22 + 2 * 8 * 4 + 2 * 2 * 12


def test_forward_and_train_flops_by_hand():
    m = MAMBA_SMALL
    ssd = ssd_scan.cost(batch=1, seq_len=2, heads=2, head_dim=4, state=2,
                        groups=1, chunk=2)[0]
    head = 2 * 4 * 10
    assert model.forward_flops(m, 1, 2, 2) == 2 * 288 + ssd + 2 * head
    assert model.train_flops_per_token(m, 2) == \
        3 * (2 * 288 + ssd + 2 * head) / 2


def test_mamba2_130m_train_flops_near_six_times_params():
    from bench import harness
    m = harness.load_json(harness.BENCH / "configs" / "mamba2-130m.json")["program"]
    per_tok = model.train_flops_per_token(m, 2048)
    # 6 x 129M parameters, plus the SSD scan's chunk products
    assert 6 * 129e6 < per_tok < 6 * 129e6 * 1.5
