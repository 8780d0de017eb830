"""Every per-layer reader named in BENCHMARK.json, on a hand-made trace of
one decode block and one mixed step: each returns a number or nothing, and
no share of a peak or a roofline passes 100%."""
import json

import numpy as np
import pytest

import _paths  # noqa: F401

import run
import xplane

BENCH = json.loads((_paths.CHECKOUT / "BENCHMARK.json").read_text())
CFG = json.loads((_paths.BENCH / "configs" / "yi-9b-24L.json").read_text())
PEAKS = json.loads((_paths.BENCH / "peaks.json").read_text())["TPU v5 lite"]


def ev(name, s, e):
    return xplane.Event(name, s, e)


@pytest.fixture
def ctx():
    layers = CFG["num_hidden_layers"]
    ops, t = [], 1.0
    for i in range(2 * layers):              # decode block of n=2
        ops.append(ev(f"paged_decode_attention_partial.12", t, t + 0.004))
        t += 0.005
    decode = ev("jit_decode_n(1)", 1.0, t)
    t = 2.0
    mixed_ops = []
    for i in range(layers):
        mixed_ops.append(ev("paged_flash_prefill.11", t, t + 0.015))
        mixed_ops.append(ev("paged_decode_attention_partial.10", t + 0.015,
                            t + 0.02))
        t += 0.03
    mixed = ev("jit_mixed(2)", 2.0, t)
    dev = xplane.Device("/device:TPU:0", ops=ops + mixed_ops,
                        modules=[decode, mixed])
    spans = [ev("chipbench.window", 0.5, 3.5),
             ev("chipbench.decode#0", 0.99, 0.995),
             ev("chipbench.mixed#1", 1.99, 1.995)]
    rec = type("R", (), {"dispatches": [
        {"kind": "decode", "n": 2, "decode_ctx": [[100, 2000]] * 2,
         "chunks": []},
        {"kind": "mixed", "n": 1, "decode_ctx": [[101, 2001]],
         "chunks": [(0, 256), (256, 200)]}]})()
    return {"cfg": CFG, "peaks": PEAKS, "chips": 1, "recorder": rec,
            "trace": xplane.Trace([dev], spans),
            "trace_window": (0.5, 3.5), "records": [], "all_records": [],
            "served": {}, "t0": 0.0, "t1": 50.0}


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_reader(ctx, metric):
    value = run.load_reader(metric["name"]).read(ctx)
    assert value is None or value >= 0
    if metric["unit"] == "%":
        assert value is None or value <= 100.0
    if metric["source"] == "device_trace":
        assert value is not None


def test_device_readers_by_hand(ctx):
    read = {m: run.load_reader(m).read(ctx) for m in (
        "step.mixed_ms", "step.decode_ms", "device.idle_share")}
    layers = CFG["num_hidden_layers"]
    assert read["step.mixed_ms"] == pytest.approx(1e3 * layers * 0.03)
    # a block of two steps: 2 x layers x 5 ms
    assert read["step.decode_ms"] == pytest.approx(1e3 * layers * 0.005)
    busy = 2 * layers * 0.004 + layers * 0.02
    assert read["device.idle_share"] == pytest.approx(100 * (1 - busy / 3.0))


def test_decode_reader_without_a_decode_block(ctx):
    ctx["recorder"].dispatches[0]["kind"] = "other"
    assert run.load_reader("step.decode_ms").read(ctx) is None


def test_client_readers_by_hand(ctx):
    """Gaps are read where they end in the window, from every request;
    time to first token from the window's requests' due times."""
    early = {"due": -30.0, "times": [-20.0, -0.5, 0.5, 1.0, 60.0],
             "prompt": [1]}
    late = {"due": 40.0, "times": [42.0, 42.2, 42.4, 42.6], "prompt": [2]}
    ctx.update(all_records=[early, late], records=[late])
    read = {m: run.load_reader(m).read(ctx) for m in (
        "ttft_p90_ms", "tpot_ms", "itl_p95_ms")}
    assert read["ttft_p90_ms"] == pytest.approx(2000.0)
    gaps = [1.0, 0.5, 0.2, 0.2, 0.2]
    assert read["tpot_ms"] == pytest.approx(1e3 * sum(gaps) / len(gaps))
    assert read["itl_p95_ms"] == pytest.approx(
        1e3 * float(np.percentile(gaps, 95)))
