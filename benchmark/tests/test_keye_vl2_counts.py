"""The keye_vl2 cell's accounting: ``train_flops_per_token`` gives the
figure on record, the selected keys a query and the expert load match
counts made by hand, the new readers read nothing without their scopes,
and the cell's files load by name and rehearse."""
import math

import pytest

import run as harness

CELL = "keye_vl2_30b_a3b.train_bf16_b4_s8192"


def _parts():
    return (harness.load_json("configs", "keye_vl2_30b_a3b"),
            harness.load_json("traffic", "train_bf16_b4_s8192"),
            harness.load_module("models", "keye_vl2"))


def test_selected_keys_a_query_match_a_count_by_hand():
    cfg, _, model = _parts()
    # query t sees t + 1 keys and keeps min(t + 1, 2048)
    by_hand = sum(min(t + 1, 2048) for t in range(8192)) / 8192
    assert model.selected_keys_per_query(cfg, 8192) == (4096.5, by_hand)
    assert by_hand == pytest.approx(1792.1, abs=0.05)
    # a row no longer than topk keeps every visible key
    assert model.selected_keys_per_query(cfg, 2048) == (1024.5, 1024.5)


def test_keye_vl2_flops_match_the_figure_on_record():
    # ISSUE 30 / PERF.md: 6 a matmul weight a token uses here (attention
    # 18.87 M, router 0.26 M, one expert of 4.72 M in expectation, the
    # 2048 x 18992 head), 4 for the indexer's 2.26 M, attention at the
    # selected pairs: 1.08 GFLOP in the four layers, 0.23 in the head
    cfg, _, model = _parts()
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    expert = 3 * 2048 * 768
    indexer = 2048 * (1024 + 64 + 16)
    kept = model.selected_keys_per_query(cfg, 8192)[1]
    pairs = (12 * 4096 * kept + 2 * 1024 * 4096.5 + 4 * 1024 * kept
             + 2 * 4096 * kept)
    layer = 6 * (attn + 2048 * 128 + 8 * 16 / 128 * expert) + 4 * indexer \
        + pairs
    want = 4 * layer + 6 * 2048 * 18992
    assert model.train_flops_per_token(cfg, 8192) == pytest.approx(want,
                                                                   rel=1e-12)
    assert 4 * layer == pytest.approx(1.08e9, rel=0.005)
    assert 6 * 2048 * 18992 == pytest.approx(0.233e9, rel=0.005)
    assert want == pytest.approx(1.32e9, rel=0.005)


def test_roofline_counts_match_hand_counts():
    cfg, mix, model = _parts()
    kept = model.selected_keys_per_query(cfg, 8192)[1]
    # one assignment a token here: top_k * held / total = 8 * 16 / 128,
    # so a grouped-product call walks 8192 rows of its sequence; 14 calls
    # a (sequence, layer) is what the step runs (3 forward, 3 replayed, 2
    # of the 3 once more inside the backward, 6 backward products)
    calls = 14 * 4 * 4
    flops, bytes_ = model.expert_matmul_work(cfg, mix, calls)
    assert flops == calls * 2 * 8192 * 2048 * 768
    weights = 16 * 2048 * 768 * 2
    assert bytes_ == calls * (weights
                              + 8192 * (2 * 2048 + 3 * 768) * 2 / 3)
    assert model.expert_matmul_work(cfg, mix, 2 * calls)[0] == 2 * flops
    # selected pairs only; 4 forward kernel calls (the replay keeps out
    # and lse) of two matmuls and 4 backward of five
    flops, bytes_ = model.sparse_attention_work(cfg, mix, 4)
    matmul = 2 * 4 * 8192 * 32 * 128 * kept
    assert flops == matmul * (2 * 4 + 5 * 4)
    assert flops == pytest.approx(1.347e13, rel=0.001)
    q, kv = 4 * 8192 * 32 * 128 * 2, 4 * 8192 * 4 * 128 * 2
    mask = 4 * 4 * 8192 * (8192 + 512) / 2
    assert bytes_ == 4 * (2 * q + 2 * kv + mask) + 4 * (4 * q + 4 * kv
                                                        + 2 * mask)
    # replayed forward kernels would count as executed
    assert model.sparse_attention_work(cfg, mix, 8)[0] == matmul * (16 + 20)


@pytest.mark.parametrize("metric", [
    "moe_ms", "moe_dispatch_ms", "moe_experts_roofline", "dsa_indexer_ms",
    "sparse_attention_ms", "sparse_attn_roofline"])
def test_the_new_readers_read_nothing_without_their_scopes(metric):
    reader = harness.load_module("layer_metrics", metric)
    ctx = {"scope_rows": [{"op_name": "jit(step_fn)/jvp(loss)/q:Linear/dot",
                           "ms": 3.0, "mosaic": False, "phase": "forward",
                           "instruction": "fusion.1"}],
           "log": lambda m: None}
    assert reader.read(ctx) is None
    assert reader.read({"scope_rows": None, "log": lambda m: None}) is None


def test_the_readers_sum_their_scopes_and_the_ragged_dot_kernels():
    cfg, mix, model = _parts()
    pre = "jit(step_fn)/jvp(loss)/blocks.0:Block/"
    rows = [
        {"op_name": pre + "moe:MoELayer/moe/moe_router/dot", "ms": 1.0},
        {"op_name": pre + "moe:MoELayer/moe/moe_dispatch/gather", "ms": 2.0},
        {"op_name": pre + "moe:MoELayer/moe/moe_experts/mul", "ms": 4.0},
        {"op_name": "ragged-dot-none", "ms": 8.0, "mosaic": True,
         "instruction": "ragged-dot-none.3"},
        {"op_name": pre + "dsa_indexer/dsa_scores/x", "ms": 16.0,
         "mosaic": True},
        {"op_name": pre + "dsa_select/dsa_threshold/x", "ms": 32.0,
         "mosaic": True},
        {"op_name": pre + "sparse_attention/sparse_fwd/x", "ms": 64.0,
         "mosaic": True},
        {"op_name": pre + "sparse_attention/transpose", "ms": 128.0},
    ]
    rows = [{"mosaic": False, "phase": "forward", "instruction": "fusion.1",
             **r} for r in rows]
    logged = []
    # two traced steps, five executions of the one ragged-dot instruction
    events = [("%ragged-dot-none.3 = bf16[18432,768]{1,0} custom-call(%a)",
               i, 1, {}) for i in range(5)]
    events.append(("%fusion.1 = f32[8]{0} fusion(%b)", 9, 1, {}))
    events.append(("%ragged-dot-metadata.2 = (s32[8]) custom-call(%c)", 10, 1,
                   {}))
    ctx = {"scope_rows": rows, "log": logged.append, "cfg": cfg, "mix": mix,
           "model": model, "peaks": harness.peak_of("TPU v5 lite"),
           "trace": {"events": events, "module_runs": 2}}

    def read(name):
        return harness.load_module("layer_metrics", name).read(ctx)

    assert read("moe_ms") == 15.0
    assert read("moe_dispatch_ms") == 3.0
    assert read("dsa_indexer_ms") == 48.0
    assert read("sparse_attention_ms") == 192.0
    flops, _ = model.expert_matmul_work(cfg, mix, 2.5)
    assert read("moe_experts_roofline") == pytest.approx(
        flops / 197e12 * 1000 / 12.0 * 100, rel=1e-6)
    assert any("2.50 ragged-dot calls" in m for m in logged)
    # a step without such a kernel has nothing to count the products by
    assert harness.load_module("layer_metrics", "moe_experts_roofline").read(
        {**ctx, "trace": {"events": events[-2:], "module_runs": 2}}) is None
    # one sparse_fwd instruction in the rows: one forward call counted
    flops, _ = model.sparse_attention_work(cfg, mix, 1)
    assert read("sparse_attn_roofline") == pytest.approx(
        flops / 197e12 * 1000 / 192.0 * 100, rel=1e-6)
    assert any("1 forward kernel calls" in m for m in logged)


def test_the_cell_loads_by_name_and_names_its_cut():
    cell, cfg, mix, model, ref, runner = harness.load_parts(CELL)
    assert (mix["batch"], mix["seq"], mix["ring"], cell["chips"]) == (
        4, 8192, 8, 1)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_local_experts": 128,
                                "vocab_size": 151936}
    assert ref.held_ids(cfg) == tuple(range(16))
    shapes = ref.param_shapes(cfg, {})
    n = sum(math.prod(s) for s, _ in shapes.values())
    assert n == pytest.approx(465.4e6, rel=0.001)
    assert set(model.param_map(cfg, {}).values()) == {
        (leaf, i) for leaf in shapes if leaf.startswith("layers.")
        for i in range(4)} | {("head.w", None), ("norm_f.g", None),
                              ("tok", None)}
    assert set(cell["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"}
