"""CPU tests of the benchmark's harness: traffic, reductions, files.

None of them touches a TPU: the traffic is plain numpy, and the metric
readers are fed a hand-made run and trace whose values are worked out
by hand below.
"""

from __future__ import annotations

import json
import math
import re
import types

import numpy as np
import pytest

from chip import counts, devtrace, harness, pctl, traffic
from chip.configs import dense_decoder

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())

CLOSED = {"loop": "closed", "slots": 64, "requests": 512,
          "prompt": {"dist": "loguniform", "min": 64, "max": 512},
          "output": {"dist": "loguniform", "min": 256, "max": 1536},
          "greedy_share": 0.5}
OPEN = {"loop": "open", "rate": 7.5, "lead_in_s": 4,
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "min": 32, "max": 1536},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 16, "max": 512},
        "greedy_share": 0.5}
SEEDS = (1, 2 ** 33 + 7)


# -- traffic ----------------------------------------------------------------


def _shape(reqs):
    return sorted((len(r.prompt), r.max_tokens) for r in reqs)


def test_grid_same_lengths_for_any_seed():
    a, b = (traffic.closed_loop(CLOSED, s, 1000) for s in SEEDS)
    assert _shape(a[0]) == _shape(b[0])          # equilibrium start
    assert sorted(len(r.prompt) for r in a[1]) == \
        sorted(len(r.prompt) for r in b[1])
    assert sorted(r.max_tokens for r in a[1]) == \
        sorted(r.max_tokens for r in b[1])
    assert [r.prompt for r in a[1][:4]] != [r.prompt for r in b[1][:4]]
    la, wa = traffic.open_loop(OPEN, SEEDS[0], 1000, 30)
    lb, wb = traffic.open_loop(OPEN, SEEDS[1], 1000, 30)
    assert sorted(len(r.prompt) for r in wa) == \
        sorted(len(r.prompt) for r in wb)
    assert sorted(r.max_tokens for r in la) == sorted(r.max_tokens for r in lb)


def test_grid_stays_in_range_and_follows_the_law():
    g = traffic.grid(OPEN["prompt"], 1001)
    assert g.min() >= 32 and g.max() <= 1536
    assert np.median(g) == 512
    lu = traffic.grid({"dist": "loguniform", "min": 64, "max": 512}, 3)
    assert list(lu) == [round(64 * 8 ** (k / 6)) for k in (1, 3, 5)]


def test_clipped_tail_keeps_the_median():
    law = {"dist": "lognormal", "median": 1020, "sigma": 1.0, "min": 16,
           "max": 1792}
    g = traffic.grid(law, 1000)
    assert np.median(g) == pytest.approx(1020, abs=3)
    # P(X > 1792) = 1 - Phi(ln(1792 / 1020)) = 0.287 of them at the cap
    assert (g == 1792).mean() == pytest.approx(0.287, abs=0.002)
    assert g.min() >= 16


def test_mix_lengths_match_their_published_statistics():
    for p in sorted((harness.HERE / "traffic").glob("*.json")):
        mix = traffic.load_mix(p.stem)
        assert mix["source"] and mix["published"], p.stem
        for key, want in mix["published"].items():
            part, stat = key.split("_")
            g = traffic.grid(mix[part], 4096)
            got = np.mean(g) if stat == "mean" else np.median(g)
            assert got == pytest.approx(want, rel=0.01), (p.stem, key)


def test_stratified_order_balances_every_round():
    order = traffic.stratified_order(64, np.random.default_rng(3))
    assert sorted(order) == list(range(64))
    for r in range(4):                            # 16 strata of 4
        assert sorted(order[16 * r:16 * r + 16] // 4) == list(range(16))


@pytest.mark.parametrize("rate,seconds", [(7.5, 30), (2.0, 17), (0.3, 1)])
def test_open_loop_offers_exactly_rate_times_seconds(rate, seconds):
    mix = dict(OPEN, rate=rate)
    for s in SEEDS:
        lead, window = traffic.open_loop(mix, s, 1000, seconds)
        assert len(window) == max(1, round(rate * seconds))
        assert len(lead) == max(1, round(rate * mix["lead_in_s"]))
        due = [r.due for r in window]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds


def test_open_loop_offers_the_same_gaps_for_any_seed():
    a, b = (traffic.arrivals(40, 10.0, np.random.default_rng(s))
            for s in SEEDS)
    ga = np.diff(np.concatenate([[0.0], a, [10.0]]))
    gb = np.diff(np.concatenate([[0.0], b, [10.0]]))
    assert np.allclose(sorted(ga), sorted(gb))
    assert not np.allclose(ga, gb)
    assert ga.sum() == pytest.approx(10.0)
    # exponential gaps: about 63% of them shorter than the mean
    assert 0.55 < (ga < 10.0 / 41).mean() < 0.7


def test_equilibrium_ages_lie_within_each_output():
    outputs = traffic.grid(CLOSED["output"], 512)
    outs, ages = traffic.equilibrium(outputs, 64, np.random.default_rng(5))
    assert ((0 <= ages) & (ages < outs)).all()
    # length-biased: the slots hold longer requests than a fresh draw
    assert outs.mean() > outputs.mean()
    initial, _ = traffic.closed_loop(CLOSED, 5, 1000)
    assert all(r.max_tokens >= 1 for r in initial)
    assert all(len(r.prompt) + r.max_tokens <= 512 + 1536 for r in initial)


def test_greedy_share_is_exact():
    _, seq = traffic.closed_loop(CLOSED, 9, 1000)
    assert sum(r.greedy for r in seq[:100]) == 50


# -- percentiles, counts ----------------------------------------------------


def test_percentile_is_a_measured_value():
    assert pctl.percentile([5, 1, 4, 2, 3], 50) == 3
    assert pctl.percentile([5, 1, 4, 2, 3], 99) == 5
    assert pctl.percentile(list(range(1, 101)), 95) == 95
    assert pctl.percentile([0.25, 0.5], 90) == 0.5


DM = dense_decoder.Dims(d_model=8, n_layers=2, n_heads=2, n_kv_heads=1,
                        head_dim=4, d_ff=16, vocab_size=32, token_vocab=32,
                        rope_theta=1e4, norm_eps=1e-5, norm="rmsnorm",
                        tied=False, max_positions=64)


def test_counts_by_hand():
    # per layer: q, o 8x8 each, k, v 8x4 each, three 8x16 maps
    assert counts.layer_matmul_params(DM) == 2 * 64 + 2 * 32 + 3 * 128
    # 100 (query, key) pairs: 4 ops x 2 layers x 2 heads x 4 dims each
    assert counts.paged_decode(DM, 100, 10) == (
        6400, 2 * (100 * 2 * 1 * 4 * 2 + 10 * 2 * 2 * 4 * 2))
    assert counts.flash_prefill(DM, [4, 8]) == (4 * 2 * 2 * 4 * (10 + 36),
                                                2 * 12 * 2 * 3 * 4 * 2)
    assert counts.decode_step_flops(DM, 100, 10) == \
        2816 * 10 + 6400                 # 2 x (2 x 576 + 8 x 32) per token
    assert counts.prefill_flops(DM, [4, 8]) == \
        2 * 2 * 576 * 12 + 2 * 8 * 32 * 2 + 2944


# -- metric readers on a hand-made run ---------------------------------------


def _h(tokens, first=None, prompt=4):
    return types.SimpleNamespace(t_tokens=tokens, t_first_token=first,
                                 prompt=[0] * prompt)


def _trace():
    E = devtrace.Event
    ops = [E("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
             100.0, 100.5, 0),
           E("%closed_call.3 = bf16[4,2,4]{2,1,0} custom-call(s32[4] %a), "
             'custom_call_target="tpu_custom_call"', 100.5, 101.0, 0),
           E("%closed_call.7 = bf16[2,2,8,4]{3,2,1,0} custom-call(), "
             'custom_call_target="tpu_custom_call"', 101.0, 101.25, 0),
           E("%while.2 = (s32[], bf16[8]{0}) while((s32[], bf16[8]) %t), "
             "condition=%c, body=%b", 101.5, 102.5, 0),
           E("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %q)", 101.5, 102.5, 0)]
    modules = [E("jit_overlap_fn(1)", 100.0, 101.0, 0),
               E("jit_prefill_fn(2)", 101.0, 101.4, 0),
               E("jit_overlap_fn(1)", 101.5, 102.0, 0)]
    spans = [E("window", 100.0, 102.0), E("engine.step", 100.9, 101.6),
             E("wait_arrival", 101.3, 101.5)]
    return devtrace.Trace(ops=ops, modules=modules, spans=spans, devices=1)


def _run(loop):
    handles = [_h([9.5, 10.5, 11.0, 12.0, 12.5]), _h([10.2, 11.7])]
    arrivals = [(10.1, 10.11, _h([10.4], 10.4)), (11.0, 11.02, _h([])),
                (11.1, 11.13, _h([11.2], 11.2))]
    return harness.Run(
        loop=loop, mix={"slots": 4}, dm=DM,
        peak={"bf16_flops": 23552.0, "hbm_bytes_per_s": 7680.0},
        setup_s=5.0, t0=10.0, t1=12.0, handles=handles, arrivals=arrivals,
        counters={"steps": 10, "slot_steps": 30, "live_token_steps": 100,
                  "queued": 0},
        prefilled=[4, 8], trace=_trace())


EXPECTED = {
    # closed loop
    ("closed", "setup_s"): 5.0,
    ("closed", "tokens_per_s"): 5 / 2.0,          # 3 + 2 tokens in (10, 12]
    ("closed", "itl_p95_ms"): 1500.0,             # gaps 1, .5, 1, 1.5
    ("closed", "ttft_p95_ms"): None,
    ("closed", "batch_occupancy"): 30 / 40,
    ("closed", "device_idle_frac.closed"): 1 - 1.75 / 2,
    ("closed", "device_idle_frac.open"): None,
    ("closed", "decode_step_ms"): 750.0,          # 1.5 s over 2 steps
    # 2 layers x (100 x 16 B of K, V + 30 x 32 B of q, out) at 7680 B/s
    ("closed", "paged_decode_roofline"): 100 * (5120 / 7680) / 0.5,
    ("closed", "decode_step_mfu"): 100 * (2816 * 30 + 6400) / (1.5 * 23552),
    # open loop
    ("open", "ttft_p95_ms"): 1000.0,              # .3, (12 - 11), .1
    ("open", "submit_lag_p95_ms"): 30.0,
    ("open", "prefill_step_ms"): 200.0,           # 0.4 s over 2 prompts
    ("open", "flash_prefill_roofline"): 100 * 0.15 / 0.25,  # bytes bound
    ("open", "prefill_step_mfu"): 100 * 31616 / (0.4 * 23552),
    ("open", "device_idle_frac.open"): 1 - 1.75 / 1.8,
    ("open", "device_idle_frac.closed"): None,
    # the decode step's readers, named apart for the open loop
    ("open", "itl_p95_ms.open"): 1500.0,
    ("open", "decode_step_ms.open"): 750.0,
    ("open", "paged_decode_roofline.open"): 100 * (5120 / 7680) / 0.5,
    ("open", "decode_step_mfu.open"): 100 * (2816 * 30 + 6400) / (1.5 * 23552),
}


@pytest.mark.parametrize("loop,name", sorted(EXPECTED))
def test_metric_reader_by_hand(loop, name):
    got = harness.reader(name)(_run(loop))
    want = EXPECTED[(loop, name)]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)


def test_reader_without_trace_reads_nothing():
    run = _run("open")
    run.trace = None
    for name in ("decode_step_ms", "paged_decode_roofline", "prefill_step_mfu",
                 "device_idle_frac.open", "flash_prefill_roofline"):
        assert harness.reader(name)(run) is None


def test_trace_breakdown_by_hand():
    tr = _trace()
    assert devtrace.busy_s(tr) == pytest.approx(1.75)
    assert dict(devtrace.top_ops(tr)) == pytest.approx(
        {"fusion.1 fusion bf16[8]": 0.5,
         "closed_call.3 custom-call bf16[4,2,4]": 0.5,
         "fusion.2 fusion bf16[8]": 0.5,
         "closed_call.7 custom-call bf16[2,2,8,4]": 0.25})     # no while
    assert devtrace.split_hlo(tr.ops[3].name)[1] == "while"
    gaps = devtrace.idle_gaps(tr)
    assert [g[0] for g in gaps] == ["wait_arrival"]
    assert gaps[0][1] == pytest.approx(0.25)
    assert devtrace.overlap([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2)


# -- files and names ----------------------------------------------------------


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_file_loads_by_name():
    for p in sorted((harness.HERE / "configs").glob("*.json")):
        cfg = harness.load_json("configs", p.name)
        assert cfg["name"] == p.stem
        assert harness.reference(cfg).dims(cfg).n_layers >= 1
    for p in sorted((harness.HERE / "traffic").glob("*.json")):
        mix = traffic.load_mix(p.stem)
        assert len(traffic.grid(mix["prompt"], 8)) == 8
        assert mix["prompt"]["max"] + mix["output"]["max"] <= mix["max_len"]
    for p in sorted((harness.HERE / "metrics").glob("*.py")):
        assert callable(harness.reader(p.stem))
    for cell in BENCH["workloads"]:
        _cell, cfg, mix, limits = harness.load_cell(cell["name"], BENCH)
        dm = harness.reference(cfg).dims(cfg)
        assert mix["max_len"] <= dm.max_positions
        assert {"logit_gap", "min_tokens", "max_requests"} <= set(limits)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_benchmark_names_and_units():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {c["name"]: c for c in BENCH["workloads"]}
    names = [c["name"] for c in BENCH["configs"]] + list(cells) + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [c["traffic"] for c in cells.values()]:
        assert NAME.match(n), n
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(cells)
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", cells), (m["name"], w)
    for w in cells:
        assert any(w in m.get("workloads", cells) for m in e2e.values()
                   if m["name"] != "setup_s")
        assert any(w in m["workloads"] for m in BENCH["per_layer"])


def test_counts_match_the_configurations():
    cfg = harness.load_json("configs", "yi-6b.json")
    dm = dense_decoder.dims(cfg)
    n = sum(math.prod(s) for s in dense_decoder.weight_shapes(dm).values())
    # 16 of Yi-6B's 32 layers (173 M weights each) plus embedding and head
    assert n == 16 * (counts.layer_matmul_params(dm) + 2 * 4096) \
        + 2 * 64000 * 4096 + 4096
