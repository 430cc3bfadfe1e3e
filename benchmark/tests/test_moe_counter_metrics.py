"""The three metrics that read the expert layers' device counters
(``moe_buffer_live_share``, ``moe_full_buffer_chunks``,
``moe_load_imbalance``) over a registry made by hand: the arithmetic, and
nothing read (with the reason logged) on a program without the counters."""
import pytest

import run as harness
from paddle_tpu import observability
from paddle_tpu.utils import monitor

METRICS = ("moe_buffer_live_share", "moe_full_buffer_chunks",
           "moe_load_imbalance")


@pytest.fixture
def registry(monkeypatch):
    """A program whose read publishes nothing new; the test fills the
    registry itself."""
    monitor.stat_reset()
    monkeypatch.setattr(observability, "read_device_counters", dict,
                        raising=False)
    yield monitor
    monitor.stat_reset()


def _publish(name, total, last=None, steps=2):
    """``total`` / ``last`` [calls][...] as the program's read writes them."""
    monitor.stat_set(f"{name}.steps", steps)
    for part, rows in (("total", total), ("last", last or total)):
        for call, row in enumerate(rows):
            if not isinstance(row, list):
                monitor.stat_set(f"{name}.{part}.{call}", row)
                continue
            for i, value in enumerate(row):
                monitor.stat_set(f"{name}.{part}.{call}.{i}", value)


def _read(name, logged=None):
    logged = [] if logged is None else logged
    return harness.load_module("layer_metrics", name).read(
        {"log": logged.append})


def _two_layers(full=(0, 0), fullest=(30, 60), load=None):
    """Two expert layers, two chunks, two steps; small buffer 100 rows,
    full 400."""
    monitor.stat_set("moe.small_buffer_rows", 100)
    monitor.stat_set("moe.full_buffer_rows", 400)
    _publish("moe.chunk_assignments", [[80, 120], [40, 60]],
             last=[[45, 70], [15, 35]])
    _publish("moe.full_buffer_chunks", list(full))
    _publish("moe.expert_load", load or [[50, 50, 50, 50], [10, 20, 30, 40]])
    _publish("moe.fullest_expert_load", list(fullest))


def test_live_share_counts_small_and_full_buffers(registry):
    """8 chunk visits (2 layers x 2 chunks x 2 steps), 300 held
    assignments: all on the small buffer 300 / 800; three of them on the
    full one 300 / (5 * 100 + 3 * 400)."""
    _two_layers()
    assert _read("moe_buffer_live_share") == pytest.approx(37.5)
    assert _read("moe_full_buffer_chunks") == 0
    _two_layers(full=(1, 2))
    logged = []
    assert _read("moe_buffer_live_share", logged) == pytest.approx(
        300 / 1700 * 100)
    assert _read("moe_full_buffer_chunks") == 1.5
    # the raw counts are logged: the newest step's chunks, the fullest
    # one's share of the small buffer
    assert any("least 15, mean 41.2, most 70" in m and "70.00 %" in m
               for m in logged)
    assert any("5 small buffers, 3 full ones" in m for m in logged)


def test_imbalance_of_an_even_and_of_a_one_expert_load(registry):
    # every step's fullest expert had a quarter of the layer's load
    _two_layers(fullest=(50, 25), load=[[50] * 4, [25] * 4])
    assert _read("moe_load_imbalance") == pytest.approx(1.0)
    # one expert got everything in the first layer; the second is even
    _two_layers(fullest=(200, 25), load=[[200, 0, 0, 0], [25] * 4])
    assert _read("moe_load_imbalance") == pytest.approx((4.0 + 1.0) / 2)
    # a layer nobody was sent to is left out, not divided by
    _two_layers(fullest=(0, 40), load=[[0] * 4, [10, 20, 30, 40]])
    assert _read("moe_load_imbalance") == pytest.approx(1.6)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_is_read_without_the_counters(metric, monkeypatch):
    """The parent of the PR that added them (no reader in the program),
    and a step that has the reader and no expert layer: None, and why."""
    monitor.stat_reset()
    monkeypatch.setattr(observability, "read_device_counters", dict,
                        raising=False)
    logged = []
    assert _read(metric, logged) is None
    assert any("no step of this process counted an expert layer" in m
               for m in logged)
    monkeypatch.delattr(observability, "read_device_counters")
    logged = []
    assert _read(metric, logged) is None
    assert any("no device counters" in m for m in logged)


def test_the_metrics_are_the_moe_cells_alone():
    cells = {"keye_vl2_30b_a3b.train_bf16_b4_s8192",
             "joyai_llm_flash.train_bf16_b2_s8192"}
    for cell in ("bert_base.train_bf16_b64_s512",
                 "gpt3_large.train_bf16_b8_s2048",
                 "evabyte.train_bf16_b1_s8192", *cells):
        names = {m["name"] for m in harness.metric_entries("per_layer", cell)}
        assert set(METRICS) <= names if cell in cells \
            else not names & set(METRICS)
