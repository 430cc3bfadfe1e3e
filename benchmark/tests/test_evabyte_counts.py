"""The evabyte cell's accounting: ``train_flops_per_token`` gives the
figure on record, and the EVA roofline count matches a count made by
hand, key by key, at a tiny shape."""
import pytest

import run as harness


def test_evabyte_flops_match_the_figure_on_record():
    # ISSUE 26 / PERF.md: 821.4 M parameters of which the embedding is not
    # multiplied; 6 a matmul weight plus attention at 1024.5 exact keys and
    # 192 summaries a query: about 5.16 GFLOP a token, 42.3 TFLOP a step
    cfg = harness.load_json("configs", "evabyte")
    model = harness.load_module("models", "evabyte")
    assert model.eva_keys_per_query(cfg, 8192) == (1024.5, 192.0)
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    want = 6 * (4 * layer + 4096 * 2560) + 12 * 4 * 4096 * 1216.5
    assert model.train_flops_per_token(cfg, 8192) == want
    assert want == pytest.approx(5.16e9, rel=0.002)
    assert want * 8192 == pytest.approx(42.3e12, rel=0.002)
    # a row inside one window is causal attention: half the row, no summary
    assert model.eva_keys_per_query(cfg, 2048) == (1024.5, 0.0)
    assert model.eva_keys_per_query(cfg, 512) == (256.5, 0.0)


def test_eva_roofline_count_matches_a_hand_count():
    cfg = {"num_attention_heads": 2, "hidden_size": 16, "chunk_size": 2,
           "window_size": 4, "num_hidden_layers": 3,
           "recompute": "per_block"}
    model = harness.load_module("models", "evabyte")
    seq, batch, D = 12, 2, 8
    # (query, key) pairs a head scores, counted one by one; the diagonal
    # counts half, as the causal half of the square does in the flash
    # count
    pairs = 0.0
    for t in range(seq):
        window = t // 4
        pairs += sum(0.5 if m == t else 1.0
                     for m in range(window * 4, t + 1))
        pairs += sum(1 for c in range(0, window * 4, 2))
    assert pairs == 3 * 4 * 4 / 2 + 4 * 2 * (0 + 1 + 2)
    flops, bytes_ = model.eva_attention_work(cfg, batch, seq)
    matmul = 2 * batch * 2 * pairs * D
    # forward twice (replayed once), two matmuls each; backward five
    assert flops == 3 * matmul * (2 * 2 + 5)
    tensor = batch * seq * 2 * D * 2            # one of q, k, v, o in bf16
    summaries = tensor / 2                      # one row a chunk of 2
    assert bytes_ == 3 * (2 * (4 * tensor + 2 * summaries)
                          + 8 * tensor + 4 * summaries)
    # one window: no summaries anywhere
    flops1, bytes1 = model.eva_attention_work(cfg, batch, 4)
    assert flops1 == 3 * 2 * batch * 2 * 8 * D * 9
    assert bytes1 == 3 * 16 * (batch * 4 * 2 * D * 2)


def test_the_roofline_metric_reads_nothing_without_the_scope():
    reader = harness.load_module("layer_metrics", "eva_attn_roofline")
    ctx = {"scope_rows": [{"op_name": "jit(step_fn)/jvp(loss)/q:Linear/dot",
                           "ms": 3.0, "mosaic": False, "phase": "forward",
                           "instruction": "fusion.1"}],
           "log": lambda m: None}
    assert reader.read(ctx) is None
    for name in ("eva_attention_ms", "eva_kernels_ms", "rms_norm_ms",
                 "rope_ms"):
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    ctx["scope_rows"] = None        # an executable without scopes at all
    assert reader.read(ctx) is None
    # and with the scope: the need over the time, in percent
    cfg = harness.load_json("configs", "evabyte")
    model = harness.load_module("models", "evabyte")
    ctx = {"scope_rows": [
        {"op_name": "jit(step_fn)/jvp(loss)/blocks.0:Block/eva_attention/"
                    "eva_fwd/pallas_call", "ms": 40.0, "mosaic": True,
         "phase": "forward", "instruction": "custom-call.1"},
        {"op_name": "jit(step_fn)/jvp(loss)/blocks.0:Block/eva_attention/"
                    "eva_pool/reduce", "ms": 20.0, "mosaic": False,
         "phase": "forward", "instruction": "fusion.2"},
        {"op_name": "jit(step_fn)/jvp(loss)/blocks.0:Block/rope/mul",
         "ms": 2.5, "mosaic": False, "phase": "forward",
         "instruction": "fusion.3"},
        {"op_name": "jit(step_fn)/transpose(jvp(loss))/norm_f:RMSNorm/"
                    "rms_norm/mul", "ms": 1.5, "mosaic": False,
         "phase": "backward", "instruction": "fusion.4"}],
        "log": lambda m: None, "cfg": cfg, "model": model,
        "mix": {"batch": 1, "seq": 8192},
        "peaks": harness.peak_of("TPU v5 lite")}
    assert harness.load_module("layer_metrics",
                               "eva_attention_ms").read(ctx) == 60.0
    assert harness.load_module("layer_metrics",
                               "eva_kernels_ms").read(ctx) == 40.0
    assert harness.load_module("layer_metrics", "rope_ms").read(ctx) == 2.5
    assert harness.load_module("layer_metrics",
                               "rms_norm_ms").read(ctx) == 1.5
    flops, _ = model.eva_attention_work(cfg, 1, 8192)
    assert reader.read(ctx) == pytest.approx(
        flops / 197e12 * 1000 / 60.0 * 100)
