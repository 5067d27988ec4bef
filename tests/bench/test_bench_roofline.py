"""Peaks by device kind, and the operations and bytes of kernel calls."""

import json

import pytest

from bench import roofline, xplane


def test_peaks_of_a_known_device():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_an_unknown_device_kind_is_an_error(tmp_path):
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks("TPU v9")
    f = tmp_path / "p.json"
    f.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(KeyError):
        roofline.peaks("TPU v5 lite", f)


def test_knn_fused_topk_cost_from_its_shapes():
    n, d, b, kp, tile = 2_002_944, 896, 64, 1024, 1024
    text = (f"(f32[{b},{kp}], s32[{b},{kp}]) custom-call(f32[{b},{d}] q, "
            f"f32[{b},1] qs, s8[{n},{d}] docs, s32[{n // tile},1,{tile}] ids,"
            f" f32[{n // tile},1,{tile}] scale)")
    shapes = xplane.operand_shapes(text)
    flops, nbytes = roofline.knn_fused_topk(shapes)
    assert flops == 2.0 * b * n * d
    assert nbytes == n * d + 4 * n + 4 * n + 4 * b * d + 4 * b + 8 * b * kp


def test_probe_cost_from_its_shapes():
    s, q, d = 64, 64, 896
    shapes = [("f32", (s, q, 1)), ("f32", (s, q, d)), ("f32", (s, 8, d)),
              ("f32", (s, q, 1)), ("f32", (s, q, 1))]
    flops, nbytes = roofline.probe_rhat_batched(shapes)
    assert flops == 2.0 * s * q * d
    assert nbytes == 4 * (3 * s * q + s * q * d + s * 8 * d)


def test_share_names_its_bound_and_never_guesses():
    peak = roofline.peaks("TPU v5 lite")
    # 819 MB in 2 ms at 819 GB/s: half the roofline, bound by memory
    share, bound = roofline.share([(1e6, 819e6)], 2e-3, peak)
    assert share == pytest.approx(50.0) and bound == "memory"
    share, bound = roofline.share([(197e12, 1.0)], 2.0, peak)
    assert share == pytest.approx(50.0) and bound == "compute"
    assert roofline.share([], 1.0, peak) == (None, None)
    assert roofline.share([(1.0, 1.0)], 0.0, peak) == (None, None)
