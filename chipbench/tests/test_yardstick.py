"""The benchmark's arithmetic on hand-built records: end-to-end metrics,
FLOP and byte counts, per-layer readers."""
from __future__ import annotations

import pytest

import common
import flops
from harness import CompileLog, ReqRec, RunData, StepRec
from traffic.gen import TrafficRequest


def _req(rid, due, times, prompt_len=10, in_window=True, start=None):
    tr = TrafficRequest(rid=rid, due=due, prompt=[1] * prompt_len,
                        output_len=len(times), in_window=in_window)
    r = ReqRec(tr=tr, due=due, times=list(times), tokens=[1] * len(times))
    r.prefill_step_start = start if start is not None else due
    return r


def _run(reqs, steps=(), w0=0.0, w1=10.0):
    dims = common.model_dims(common.load_json(
        common.HERE / "configs" / "granite-3-2b.json"))
    return RunData(cell={"name": "x"}, dims=dims,
                   peaks=common.peaks("TPU v5 lite"), reqs=list(reqs),
                   steps=list(steps), w0=w0, w1=w1, setup_s=12.5,
                   compiles=CompileLog())


def test_tbt_mean_sees_one_stall():
    # 4 requests with 101 tokens 20 ms apart: 400 gaps of 20 ms
    reqs = [_req(f"r{i}", 0.5, [1.0 + 0.02 * k for k in range(101)])
            for i in range(4)]
    run = _run(reqs)
    assert common.metric_reader("tbt_mean_ms")(run) == pytest.approx(20.0)
    assert common.metric_reader("setup_s")(run) == 12.5
    # one 1 s stall in every request's stream (an exclusive prefill): each
    # request gets one 1020 ms gap among its 100
    for r in reqs:
        r.times = r.times[:50] + [t + 1.0 for t in r.times[50:]]
    run = _run(reqs)
    assert common.metric_reader("tbt_mean_ms")(run) == pytest.approx(30.0)


def test_gaps_count_by_their_later_token():
    r = _req("r", 0.0, [9.9, 10.05, 10.1])
    run = _run([r], w0=0.0, w1=10.0)
    assert run.gaps() == []            # both later tokens fall after w1
    run = _run([r], w0=0.0, w1=10.06)
    assert run.gaps() == [pytest.approx(0.15)]


def test_decode_flops_by_hand_granite():
    d = common.model_dims(common.load_json(
        common.HERE / "configs" / "granite-3-2b.json"))
    # per layer: q 2048x2048, k and v 2048x512 each, o 2048x2048,
    # mlp 3 x 2048x8192
    per_layer = 2048 * 2048 * 2 + 2048 * 512 * 2 + 3 * 2048 * 8192
    assert flops.matmul_params(d) == 40 * per_layer
    ctx = 1000
    hand = 2 * 40 * per_layer + 2 * 2048 * 49155 \
        + 4 * 40 * 32 * 64 * (ctx + 1)
    assert flops.decode_flops(d, [ctx]) == hand
    f, b = flops.paged_attention_cost(d, [ctx])
    assert f == 4 * 40 * 32 * 64 * 1001
    # K and V of 8 heads x 64 dims in bf16 per live token, per layer,
    # plus the query read and output write of 32 heads
    assert b == 40 * (2 * 8 * 64 * 2 * 1001 + 2 * 32 * 64 * 2)


# chatglm3-6b's published sizes (hf:THUDM/chatglm3-6b config.json), the
# second model PERF.md plans a cell for
CHATGLM3_6B = {"num_layers": 28, "hidden_size": 4096,
               "num_attention_heads": 32, "multi_query_group_num": 2,
               "kv_channels": 128, "ffn_hidden_size": 13696,
               "padded_vocab_size": 65024, "seq_length": 8192,
               "tie_word_embeddings": False}


def test_decode_flops_by_hand_chatglm():
    d = common.model_dims(CHATGLM3_6B)
    assert (d["n_layers"], d["n_kv_heads"], d["head_dim"]) == (28, 2, 128)
    per_layer = 4096 * 4096 * 2 + 4096 * 256 * 2 + 3 * 4096 * 13696
    hand = 2 * (2 * 28 * per_layer + 2 * 4096 * 65024) \
        + 4 * 28 * 32 * 128 * (5001 + 3001)
    assert flops.decode_flops(d, [5000, 3000]) == hand


def test_prefill_flops_counts_real_tokens_only():
    d = common.model_dims(common.load_json(
        common.HERE / "configs" / "granite-3-2b.json"))
    P = 300
    hand = 2 * flops.matmul_params(d) * P \
        + 4 * 40 * 32 * 64 * (P * (P + 1) // 2) + 2 * 2048 * 49155
    assert flops.prefill_flops(d, P) == hand


def test_step_readers():
    steps = [StepRec(0.5, 0.6, [100], [], 0.1, 0),
             StepRec(1.0, 1.2, [300, 200], [], 0.3, 2),
             StepRec(1.2, 1.25, [], [10, 20, 30], 0.4, 2),
             StepRec(1.25, 1.3, [], [11, 21], 0.5, 5)]
    reqs = [_req("a", 1.0, [1.2, 1.25], start=1.0),
            _req("b", 0.9, [1.2, 1.25, 1.3], start=1.0)]
    run = _run(reqs, steps, w0=1.0, w1=2.0)
    read = common.metric_reader
    assert read("decode_batch_mean")(run) == pytest.approx(2.5)
    assert read("kv_device_used_pct")(run) == pytest.approx(40.0)
    assert read("step_ms.prefill")(run) == pytest.approx(200.0)
    assert read("kv_layer_moves_per_req")(run) == pytest.approx(2.5)
    mfu = read("step_mfu.prefill")(run)
    work = flops.prefill_flops(run.dims, 300) \
        + flops.prefill_flops(run.dims, 200)
    assert mfu == pytest.approx(100 * work / (0.2 * 197e12))
    assert read("compiles_in_window")(run) == 0.0
    # no trace: the trace readers find nothing and say nothing
    assert read("device_idle_pct")(run) is None
    assert read("paged_attention_roofline")(run) is None


def test_every_listed_metric_has_a_reader():
    bench = common.benchmark()
    for m in bench["per_layer"]:
        assert callable(common.metric_reader(m["name"]))
    for m in bench["end_to_end"]:
        assert callable(common.metric_reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        common.peaks("TPU v9000")
