"""FLOP and byte counts of the per-layer metrics, as the configurations'
families give them, against the sizes worked out by hand."""
import json
import types

import pytest

from bench.lib.peaks import PEAKS, peaks_for
from bench.lib.spec import BENCH, family, load_module


def _cfg(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def _metric(name):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "test_metric_" + name.replace(".", "_"))


def test_repro_100m_sizes():
    cfg = _cfg("repro-100m")
    fam = family(cfg)
    assert fam.n_params(cfg) == 128_994_048
    # with no attention, 6 FLOPs per matrix-product weight
    assert fam.model_flops_per_token(cfg, 0) == 6 * 128_974_848


def test_repro_100m_model_flops_per_token_and_step():
    cfg = _cfg("repro-100m")
    f = family(cfg).model_flops_per_token(cfg, 1024)
    # 6 x 128,974,848 + 12 * L * H * d_head * S
    assert f == 6 * 128_974_848 + 12 * 12 * 12 * 64 * 1024 == 887_095_296
    assert 8 * 1024 * f == pytest.approx(7.267e12, rel=1e-3)


def test_codec_interface_bytes():
    roof = _metric("codec_roofline.train")
    cfg = _cfg("repro-100m")
    assert roof.interface_bytes(family(cfg), cfg) == 8 * 128_994_048


def test_qwen_sizes_and_decode_bytes():
    cfg = _cfg("qwen1.5-0.5b")
    fam = family(cfg)
    assert fam.n_params(cfg) == 463_987_712
    assert fam.kv_bytes_per_position(cfg, 2) == 2 * 24 * 16 * 64 * 2
    dec = _metric("decode_hbm_roofline.serve")
    assert dec.least_bytes(fam, cfg, 3, 1000) == (3 * 463_987_712 * 2
                                                  + 1000 * 98_304)


def test_peaks_table_has_sources_and_refuses_unknown_devices():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(KeyError):
        peaks_for("cpu")


def _reading(codec_s_per_step=None):
    # 10 steps of 8 x 1024 tokens in a 1.1 s window
    dev = types.SimpleNamespace(
        module_count=lambda part: 10.0,
        op_seconds=lambda pred: 10 * codec_s_per_step)
    trace = types.SimpleNamespace(devices=[dev], scope=lambda n: n,
                                  window_s=lambda: 1.1)
    cfg = _cfg("repro-100m")
    return types.SimpleNamespace(
        trace=trace, facts={"chips": 1}, peaks=PEAKS["TPU v5 lite"],
        config=cfg, mix={"batch": 8, "seq_len": 1024}, family=family(cfg))


def test_metric_readers_arithmetic():
    r = _reading(codec_s_per_step=2.52e-3)
    assert _metric("codec_ms_per_step.train").read(r) == pytest.approx(2.52)
    # 1.032 GB at 819 GB/s is 1.26 ms: half of 2.52 ms
    assert _metric("codec_roofline.train").read(r) == pytest.approx(
        50.0, rel=1e-3)
    assert _metric("mfu.train").read(r) == pytest.approx(
        100 * 887_095_296 * 81_920 / 1.1 / 197e12)


def test_readers_find_nothing_to_read_and_return_none():
    r = _reading(codec_s_per_step=0.0)
    assert _metric("codec_roofline.train").read(r) is None
    assert _metric("codec_ms_per_step.train").read(r) is None
