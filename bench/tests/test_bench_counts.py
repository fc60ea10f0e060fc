"""Operation and byte counts against hand counts."""

import json
from pathlib import Path

from bench import counts

CONF = json.loads((Path(__file__).parents[1] / "configs" /
                   "minicpm-2b.json").read_text())


def test_minicpm_weight_and_kv_bytes():
    d = counts.decoder(CONF)
    # the weights the program serves: bf16, vocabulary padded to 122,880,
    # float32 norm gains (the same count a v5e reported when serving it)
    assert d.served_weight_bytes() == 5_450_720_256
    assert d.kv_bytes_per_token() == 2 * 40 * 2304 * 2 == 368_640
    # a step reads the unpadded LM head and every layer once
    assert d.step_weight_bytes() == (
        40 * (4 * 2304 * 2304 + 3 * 2304 * 5760) + 122_753 * 2304) * 2


def test_decode_and_prefill_by_hand():
    d = counts.Decoder(d_model=8, d_ff=16, layers=2, heads=2, kv_heads=1,
                       head_dim=4, vocab=10, vocab_padded=16, tied=True,
                       itemsize=2)
    layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    flops, nbytes = d.decode_step([3, 5])
    assert flops == 2 * (2 * 2 * layer + 2 * 8 * 10) + 4 * 2 * 2 * 4 * (4 + 6)
    kv = 2 * 2 * 1 * 4 * 2
    assert nbytes == (2 * layer + 10 * 8) * 2 + (3 + 1 + 5 + 1) * kv + \
        2 * (8 * 2 + 10 * 4)
    flops, nbytes = d.prefill_chunk(tokens=2, start=3)
    assert flops == 2 * 2 * 2 * layer + 4 * 2 * 2 * 4 * (4 + 5) + 2 * 8 * 10
    assert nbytes == (2 * layer + 80) * 2 + 3 * kv + 2 * kv + 2 * 8 * 2 + 40


def test_tucker_contractions_count_unpadded_bytes():
    n, r = 1024, (10, 10, 10)
    cs = counts.hooi_contractions(n, r, n_iter=10)
    assert len(cs) == 3 + 8 * 10 + 3 + 3
    t1 = cs[3]
    assert t1.spec == "mnp,pk->mnk"
    assert t1.flops == 2 * n ** 3 * 10
    # T read once, C read once, the (n, n, 10) result written once: the
    # rank-10 mode is not padded to a lane of 128
    assert t1.nbytes == 4 * (n ** 3 + n * 10 + n * n * 10)
    gram = cs[0]
    assert gram.flops == 2 * n ** 4 and gram.nbytes == 4 * (n ** 3 + n * n)
    y3b = cs[3 + 6]
    assert y3b.spec == "inp,nj->ijp" and y3b.nbytes == 4 * (
        10 * n * n + n * 10 + 10 * 10 * n)
