"""The harness finds cells, configurations and per-layer metrics by
name; it refuses an unknown chip and a missing one; the FLOP accounting
gives the figure on record; a broken timed path comes out not correct."""
import argparse
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import run as harness

BENCH = harness.HERE
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads")))


def test_new_files_are_found_by_name_without_an_edit(tmp_path):
    root = tmp_path / "benchmark"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "configs" / "bert_base.json").read_text())
    cfg.update(name="bert_wide", num_hidden_layers=3)
    (root / "configs" / "bert_wide.json").write_text(json.dumps(cfg))
    mix = json.loads(
        (root / "traffic" / "train_bf16_b64_s512.json").read_text())
    mix.update(batch=32, seq=256)
    (root / "traffic" / "train_bf16_b32_s256.json").write_text(
        json.dumps(mix))
    cell = json.loads((root / "workloads" /
                       "bert_base.train_bf16_b64_s512.json").read_text())
    cell.update(config="bert_wide", traffic="train_bf16_b32_s256")
    (root / "workloads" / "bert_wide.train_bf16_b32_s256.json").write_text(
        json.dumps(cell))
    (root / "layer_metrics" / "wait_ms.p50.py").write_text(
        "import statistics\n\n\ndef read(ctx):\n"
        "    w = ctx['spans'].get('wait')\n"
        "    return statistics.median(w) if w else None\n")

    got_cell, got_cfg, got_mix = harness.load_cell(
        "bert_wide.train_bf16_b32_s256", root=str(root))
    assert got_cfg["num_hidden_layers"] == 3 and got_mix["seq"] == 256
    assert got_cell["runner"] == "train_step"
    reader = harness.load_module("layer_metrics", "wait_ms.p50", str(root))
    assert reader.read({"spans": {"wait": [1.0, 3.0, 2.0]}}) == 2.0
    assert reader.read({"spans": {}}) is None
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def test_unknown_device_kind_is_an_error():
    assert harness.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peak_of("TPU v9 imaginary")


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout, out.stdout
    assert "needs" in out.stderr


def test_gpt3_large_flops_match_the_figure_on_record():
    # BENCH_r05.json: MFU 0.501 at 792.0 ms a step of 16 x 1024 on a
    # 197 TFLOP/s chip, i.e. 78.1 TFLOP a step
    cfg = harness.load_json("configs", "gpt3_large")
    gpt = harness.load_module("models", "gpt")
    per_step = gpt.train_flops_per_token(cfg, 1024) * 16 * 1024
    assert per_step == pytest.approx(0.501 * 0.7920 * 197e12, rel=0.01)
    assert per_step == pytest.approx(78.1e12, rel=0.01)


def test_every_cell_of_BENCHMARK_json_has_its_files():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell, cfg, mix = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        for kind, name in (("models", cell["model"]),
                           ("reference", cfg["family"]),
                           ("runners", cell["runner"])):
            assert os.path.isfile(os.path.join(BENCH, kind, name + ".py"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
        assert set(m.get("workloads", [])) <= {
            w["name"] for w in bench["workloads"]}


def _args(cell):
    return argparse.Namespace(workload=cell, seed=7, seconds=0.5, trace=0,
                              keep_trace=None)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_rehearsal_is_correct(cell):
    assert harness.run_cell(_args(cell), rehearse=True)["correct"] is True


def _broken(cell, fault):
    """The cell's own runner with the timed path broken underneath."""
    real = harness.load_module("runners", harness.load_json(
        "workloads", cell)["runner"])
    broken = types.SimpleNamespace(**{
        k: getattr(real, k) for k in dir(real) if not k.startswith("_")})
    if fault == "part_of_the_batch_left_out":
        def feed(state, ids, labels):
            half = ids.shape[0] // 2
            ids, labels = ids.copy(), labels.copy()
            ids[half:], labels[half:] = ids[:half], labels[:half]
            return real.feed(state, ids, labels)
        broken.feed = feed
    elif fault == "state_returned_unchanged":
        # a learning rate of zero underneath: the step runs and hands
        # back the parameters it was given
        def build(*args):
            state = real.build(*args)
            opt = (state["step"].optimizer if "step" in state
                   else state["prog"]._optimizer[0])
            opt.set_lr(0.0)
            return state
        broken.build = build
    return broken


@pytest.mark.parametrize("fault", ["part_of_the_batch_left_out",
                                   "state_returned_unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    result = harness.run_cell(_args(cell), rehearse=True,
                              runner=_broken(cell, fault))
    assert result["correct"] is False
