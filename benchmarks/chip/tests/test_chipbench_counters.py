"""The FLOP and byte counters against hand counts for both configurations."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, peaks  # noqa: E402

counters = harness.Files(BENCH.parents[1]).module("families", "dense")
Dims = counters.Dims


def _dims(name):
    return Dims.from_config(
        json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def test_olmo_stage_train_counts():
    m = _dims("olmo-1b-stage8")
    # per layer: q, k, v, o 4 x 2048^2 and the gated MLP 3 x 2048 x 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert counters.layer_matmul_params(m) == layer == 67_108_864
    # tied: the embedding is the output projection, counted once
    assert counters.param_count(m) == 8 * layer + 50304 * 2048
    b, s = 7, 2048
    fwd = 2 * b * s * (8 * layer + 50304 * 2048)
    attn = 8 * 2 * 2 * 16 * 128 * (b * s * (s + 1) / 2)
    w = counters.train_step(m, b, s)
    assert w.flops == pytest.approx(3 * (fwd + attn))
    # 4.04 GFLOP a token, 0.20 of it attention
    assert w.flops / (b * s) == pytest.approx(4.04e9, rel=2e-3)
    p = counters.param_count(m)
    assert w.bytes == p * (3 * 2 + 2 + 2 + 8 + 2 + 8)


def test_yi_prefill_counts():
    m = _dims("yi-9b")
    layer = (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
    assert counters.layer_matmul_params(m) == layer
    # untied: input embedding and output projection both count as params
    assert counters.param_count(m) == 48 * layer + 2 * 64000 * 4096
    b, s = 4, 4096
    w = counters.prefill(m, b, s)
    flops = 2 * b * s * (48 * layer + 64000 * 4096)
    flops += 48 * 2 * 2 * 32 * 128 * b * s * (s + 1) / 2
    assert w.flops == pytest.approx(flops)
    assert w.flops / (b * s) == pytest.approx(18.74e9, rel=1e-3)
    assert w.bytes == counters.param_count(m) * 2 + b * s * 64000 * 2
    t, bound = w.roofline_s(peaks.peaks_for("TPU v5 lite"), chips=4)
    assert bound == "compute"
    assert t == pytest.approx(w.flops / (4 * 197e12))


def test_decode_counts_only_active_requests_and_their_cache():
    m = _dims("olmo-1b")
    kv = 16 * 2 * 16 * 128 * 2  # 131 kB a token
    assert counters.kv_bytes_per_token(m) == kv
    weights = counters.matmul_params(m) * 2
    one = counters.decode_step(m, [9])
    assert one.bytes == weights + kv * (10 + 1)
    assert one.flops == pytest.approx(
        2 * counters.matmul_params(m) + 16 * 2 * 2 * 16 * 128 * 10)
    # prompt 3, 2 new tokens: active at positions 0..3; prompt 2, 1 new
    # token: active at 0..1.  Steps 4, 5 run no one's work.
    batch = counters.decode_batch(m, [3, 2], [2, 1], steps=6)
    want = (counters.decode_step(m, [0, 0]) + counters.decode_step(m, [1, 1])
            + counters.decode_step(m, [2]) + counters.decode_step(m, [3]))
    assert batch == want
    t, bound = one.roofline_s(peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(one.bytes / 819e9)
