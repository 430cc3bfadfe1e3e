"""The joyai_llm_flash cell's accounting: the configuration file against
the published config and the cut, the parameter count, ``train_flops_per_
token`` against a count by hand, the two work functions, the new readers
on rows made by hand, and the cell's files loading by name."""
import math

import pytest

import run as harness

CELL = "joyai_llm_flash.train_bf16_b2_s8192"


def _parts():
    return (harness.load_json("configs", "joyai_llm_flash"),
            harness.load_json("traffic", "train_bf16_b2_s8192"),
            harness.load_module("models", "joyai_llm_flash"))


def test_the_configuration_states_its_cut_and_nothing_else():
    cfg, mix, _ = _parts()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16160)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["held_experts"] == {"first": 0, "count": 16, "of": 256}
    # the widths, the router and the MTP depth are the published ones
    assert [cfg[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
        "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
        "num_nextn_predict_layers", "routed_scaling_factor", "scoring_func",
        "topk_method", "rope_theta", "rope_interleave")] == [
        2048, 7168, 768, 1536, 512, 128, 64, 128, 32, 8, 1, 1, 1, 2.5,
        "sigmoid", "noaux_tc", 32000000, True]
    assert (mix["batch"], mix["seq"], mix["ring"]) == (2, 8192, 8)
    assert cfg["source"] == ("https://huggingface.co/jdopensource/"
                             "JoyAI-LLM-Flash/blob/main/config.json")


def test_the_parameter_count_is_the_one_on_record():
    # ISSUE 32: MLA 26.35 M a layer; 107.09 M an expert layer here; the
    # dense layer 70.39 M; the MTP module 115.48 M; embedding and head
    # 66.19 M: 680.4 M
    cfg, _, model = _parts()
    ref = harness.load_module("reference", "joyai_llm_flash")
    shapes = ref.param_shapes(cfg, {})
    n = sum(math.prod(s) for s, _ in shapes.values())
    mla = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
           + 4096 * 2048 + 1536 + 512)
    assert mla == pytest.approx(26.35e6, rel=5e-4)
    expert_layer = (mla + 2 * 2048 + 2048 * 256 + 256
                    + 17 * 3 * 2048 * 768)
    assert expert_layer == pytest.approx(107.09e6, rel=5e-4)
    dense = mla + 2 * 2048 + 3 * 2048 * 7168
    mtp = expert_layer + 4096 * 2048 + 3 * 2048
    assert n == dense + 4 * expert_layer + mtp + 2 * 16160 * 2048 + 2048
    assert n == 680441088
    assert ref.held_ids(cfg) == tuple(range(16))
    # every program parameter is one reference leaf, the stacked ones
    # block by block, and nothing is left over
    assert set(model.param_map(cfg, {}).values()) == {
        (leaf, i) for leaf in shapes if leaf.startswith("layers.")
        for i in range(4)} | {(leaf, None) for leaf in shapes
                              if not leaf.startswith("layers.")}


def test_flops_a_token_match_a_count_by_hand():
    # ISSUE 32: 3.40 GFLOP a token, 44 % of it attention's pairs
    cfg, _, model = _parts()
    mla = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    expert = 3 * 2048 * 768
    expert_layer = mla + 2048 * 256 + expert + 8 * 16 / 256 * expert
    dense = mla + 3 * 2048 * 7168
    pairs = 3 * 2 * 32 * (192 + 128) * 8193 / 2
    want = (6 * (dense + 5 * expert_layer + 4096 * 2048 + 2 * 2048 * 16160)
            + 6 * pairs)
    assert model.train_flops_per_token(cfg, 8192) == pytest.approx(
        want, rel=1e-12)
    assert want == pytest.approx(3.40e9, rel=0.005)
    assert 6 * pairs / want == pytest.approx(0.44, abs=0.005)
    # at half the row the pairs halve and nothing else moves
    assert (model.train_flops_per_token(cfg, 8192)
            - model.train_flops_per_token(cfg, 4096)) == pytest.approx(
        6 * 3 * 2 * 32 * 320 * 2048, rel=1e-9)


def test_roofline_counts_match_hand_counts():
    cfg, mix, model = _parts()
    # six blocks; forward two products (192 and 128 wide), backward five
    # (192 / 128 / 128 / 192 / 192), half the square
    pairs = 2 * 32 * 8192 * 8193 / 2
    flops, bytes_ = model.mla_attention_work(cfg, mix, 6)
    assert flops == 2 * pairs * (6 * 320 + 6 * 832)
    assert flops == pytest.approx(2.97e13, rel=0.005)
    rows = 2 * 8192 * 32 * 2
    assert bytes_ == 6 * rows * 640 + 6 * rows * 1280
    # replayed forward kernels would count as executed
    assert model.mla_attention_work(cfg, mix, 12)[0] == \
        2 * pairs * (12 * 320 + 6 * 832)
    # compute-bound: the bytes' time is under an eighth of the FLOPs'
    peaks = harness.peak_of("TPU v5 lite")
    assert bytes_ / peaks["hbm_bytes_per_s"] * 8 < \
        flops / peaks["bf16_flops_per_s"]
    # half an assignment a token here: 8 * 16 / 256
    calls = 14 * 2 * 5
    flops, bytes_ = model.expert_matmul_work(cfg, mix, calls)
    assert flops == calls * 2 * 4096 * 2048 * 768
    assert bytes_ == calls * (16 * 2048 * 768 * 2
                              + 4096 * (2 * 2048 + 3 * 768) * 2 / 3)


@pytest.mark.parametrize("metric", ["mla_attention_ms", "mla_attn_roofline",
                                    "mtp_ms"])
def test_the_new_readers_read_nothing_without_their_scopes(metric):
    reader = harness.load_module("layer_metrics", metric)
    ctx = {"scope_rows": [{"op_name": "jit(step_fn)/jvp(loss)/q:Linear/dot",
                           "ms": 3.0, "mosaic": False, "phase": "forward",
                           "instruction": "fusion.1"}],
           "log": lambda m: None}
    assert reader.read(ctx) is None
    assert reader.read({"scope_rows": None, "log": lambda m: None}) is None


def test_the_readers_sum_their_scopes():
    cfg, mix, model = _parts()
    pre = "jit(step_fn)/jvp(loss)/blocks.0:Block/attn:MLAttention/"
    mtp = "jit(step_fn)/transpose(jvp(loss))/mtp:MTPModule/mtp/"
    rows = [
        {"op_name": pre + "mla_attention/flash_fwd/x", "ms": 1.0,
         "mosaic": True},
        {"op_name": pre + "mla_attention/concatenate", "ms": 2.0},
        {"op_name": pre + "rope/mul", "ms": 4.0},
        {"op_name": mtp + "block:Block/attn:MLAttention/mla_attention/"
                          "flash_bwd_dkv/x", "ms": 8.0, "mosaic": True},
        {"op_name": mtp + "linear_cross_entropy/dot", "ms": 16.0},
        # GPT's attention: a flash kernel outside the latent scope
        {"op_name": "jit(step_fn)/jvp(loss)/scaled_dot_product_attention/"
                    "flash_fwd/x", "ms": 32.0, "mosaic": True},
    ]
    rows = [{"mosaic": False, "phase": "forward", "instruction": "fusion.1",
             **r} for r in rows]
    logged = []
    ctx = {"scope_rows": rows, "log": logged.append, "cfg": cfg, "mix": mix,
           "model": model, "peaks": harness.peak_of("TPU v5 lite")}

    def read(name):
        return harness.load_module("layer_metrics", name).read(ctx)

    assert read("mla_attention_ms") == 11.0
    assert read("mtp_ms") == 24.0
    # one flash_fwd under the scope: one forward call counted
    flops, _ = model.mla_attention_work(cfg, mix, 1)
    assert read("mla_attn_roofline") == pytest.approx(
        flops / 197e12 * 1000 / 11.0 * 100, rel=1e-6)
    assert any("1 forward kernel calls" in m for m in logged)


def test_the_cell_loads_by_name_and_is_in_BENCHMARK_json():
    cell, cfg, mix, model, ref, runner = harness.load_parts(CELL)
    assert (mix["batch"], mix["seq"], cell["chips"]) == (2, 8192, 1)
    assert cell["optimizer"]["lr"] == 2e-4 and cell["warm_steps"] == 2
    # one member without its group holds its routers still (``assumed``)
    assert cfg["train_router"] is False and "train_router" in cfg["assumed"]
    assert set(cell["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"}
    names = {m["name"] for m in harness.metric_entries("per_layer", CELL)}
    assert {"mla_attention_ms", "mla_attn_roofline", "mtp_ms", "moe_ms",
            "moe_dispatch_ms", "moe_experts_roofline", "rms_norm_ms",
            "rope_ms", "mosaic_kernels_ms", "flash_fwd_ms", "flash_bwd_ms",
            "opt_state_init_s", "step_python_ms"} <= names
    assert "flash_attn_roofline" not in names
    assert {m["name"] for m in harness.metric_entries("end_to_end", CELL)} \
        == {"tokens_per_s_per_chip", "setup_s"}
