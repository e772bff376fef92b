"""Work counts against hand counts at a tiny configuration, the pair count
against a brute-force count over real packed rows, the peaks table, and the
traffic's promise that every seed gets the same work."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import peaks, traffic, work  # noqa: E402

# D=8, L=2, H=2, KV=1, hd=4, F=16, V=10, SwiGLU, RMSNorm, tied head
TINY = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
        "mlp": "gated", "norm": "rmsnorm", "tie_word_embeddings": True}


def test_parameter_counts_by_hand():
    # per layer: wq 8*8 + wo 8*8 + wk 8*4 + wv 8*4 + wi/wg/wd 3*8*16 = 576 matmul
    # weights, + two norm scales of 8; the tied head 8*10 counts once
    assert work.matmul_params(TINY) == 2 * 576 + 80
    assert work.param_count(TINY) == 80 + 2 * (576 + 16) + 8


def test_untied_head_and_layernorm_add_their_tensors():
    conf = dict(TINY, tie_word_embeddings=False, norm="layernorm", mlp="dense")
    # dense MLP: 2*8*16 per layer; LayerNorm biases 2*8 per layer + final 8
    assert work.matmul_params(conf) == 2 * (64 + 64 + 32 + 32 + 256) + 80
    assert work.param_count(conf) == 80 + 80 + 2 * (448 + 32) + 16


def test_live_pairs_by_hand():
    lens = np.array([3, 2])
    assert work.live_pairs(lens, causal=True) == 6 + 3
    assert work.live_pairs(lens, causal=False) == 9 + 4


def test_flops_and_bytes_by_hand():
    live, pairs = 5, 9
    assert work.model_flops(TINY, live, pairs) == 6 * 1232 * 5 + 12 * 2 * 2 * 4 * 9
    assert work.attention_fwd(TINY, live, pairs, 2) == (4 * 4 * 2 * 9 * 2, 5 * 2 * 6 * 4 * 2)
    assert work.attention_bwd(TINY, live, pairs, 2) == (8 * 4 * 2 * 9 * 2, 5 * 2 * 12 * 4 * 2)
    assert work.stats_bytes(TINY, 3) == 5 * 4 * 1272
    assert work.update_bytes(TINY) == 40 * 1272


def test_roofline_takes_the_binding_peak():
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_pct(200.0, 10.0, 4.0, peak) == pytest.approx(50.0)
    assert work.roofline_pct(100.0, 40.0, 4.0, peak) == pytest.approx(100.0)
    assert work.roofline_pct(1.0, 1.0, 0.0, peak) is None


@pytest.mark.parametrize("causal", [True, False])
def test_pairs_match_a_brute_force_count_over_packed_rows(tmp_path, causal):
    from benchmarks.chip import program

    tr = {"seq_len": 64, "rows": 4, "layout_seed": 3, "corpus_tokens": 3000,
          "docs": {"law": "lognormal", "median": 20, "sigma": 1.0, "min": 2, "max": 200},
          "markov": {"branching": 4, "probs": [0.55, 0.25, 0.15, 0.05]}}
    program.write_corpus(tr, 50, 7, tmp_path / "c")
    ds = program.dataset(str(tmp_path / "c"), tr)
    batch = ds.next_batch(8)
    pos, seg = batch["positions"], batch["segments"]
    ok = (pos[:, :, None] >= 0) & (pos[:, None, :] >= 0) & (seg[:, :, None] == seg[:, None, :])
    if causal:
        ok &= pos[:, None, :] <= pos[:, :, None]
    pieces = program.piece_lengths(ds, 0, 8)
    assert work.live_pairs(pieces, causal) == int(ok.sum())
    assert int(pieces.sum()) == int((pos >= 0).sum())


def test_every_seed_gets_the_same_document_lengths():
    tr = {"layout_seed": 0, "corpus_tokens": 5000,
          "docs": {"law": "lognormal", "median": 30, "sigma": 1.2, "min": 2, "max": 500},
          "markov": {"branching": 4, "probs": [0.55, 0.25, 0.15, 0.05]}}
    a, b = list(traffic.corpus(tr, 100, 1)), list(traffic.corpus(tr, 100, 2**31 + 5))
    assert [len(d) for d in a] == [len(d) for d in b]
    assert any(not np.array_equal(x, y) for x, y in zip(a, b))
    assert sum(len(d) - 1 for d in a) >= 5000


def test_peaks_know_the_v5e_and_refuse_the_rest():
    assert peaks.peaks("TPU v5 lite") == {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
