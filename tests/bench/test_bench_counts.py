"""Step FLOPs and bytes from shapes, and the peak table."""
import pytest

from benchmarks.chip import counts, reference, spec


def test_tiny_rgcn_counts_by_hand():
    # one relation a -> b, 2 seeds of b, fanout 3, one layer; inputs:
    # 2 b rows (self) + 6 drawn a rows; a is a learnable table (width 4),
    # b has features (width 5); hidden 2; 3 classes
    layers = reference.plan([("a", "r", "b")], [3], {"b": 2})
    assert layers[0].src == (("a", 6), ("b", 2))
    c = counts.step_counts(layers, {"a": 4, "b": 5}, {"a": 4}, hidden=2,
                           head={"kind": "nc", "batch": 2, "classes": 3},
                           dense_params=10)
    enc_a, enc_b = 2 * 6 * 4 * 2, 2 * 2 * 5 * 2        # 96, 40
    self_mm = 2 * 2 * 2 * 2                             # 16
    agg, rel_mm = 2 * 2 * 3 * 2, 2 * 2 * 2 * 2          # 24, 16
    head = 2 * 2 * 2 * (2 + 3)                          # 40
    fwd = enc_a + enc_b + self_mm + agg + rel_mm + head
    bwd = 2 * enc_a + enc_b + 2 * self_mm + agg + 2 * rel_mm + 2 * head
    assert c["flops"] == fwd + bwd
    # rows read: 6 x 16 B (a) + 2 x 20 B (b); a's gradient rows written
    # and read (2 x 6 x 16) and its adagrad rows read and written
    # (2 x 6 x 20); CSR: 2 rows x 8 B + 6 slots x 4 B; dense 7 x 4 x 10
    assert c["bytes"] == 96 + 40 + 192 + 240 + 16 + 24 + 280


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")
