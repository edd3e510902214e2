"""Performance attribution plane (obs/perf.py + tools/perf_ledger.py;
docs/performance.md): op-class classification, staged input-pipeline
timers under BOTH host loaders, the analytic-vs-AOT FLOP cross-check,
perf-ledger append/import/regression-gate, the kernel-gap audit, and
the report/timeline surfaces."""

import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pytorch_distributed_train_tpu.config import (  # noqa: E402
    DataConfig,
    ModelConfig,
    TrainConfig,
)
from pytorch_distributed_train_tpu.obs import perf as perf_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import (  # noqa: E402
    get_registry,
)
from pytorch_distributed_train_tpu.utils import flops as flops_lib  # noqa: E402
from pytorch_distributed_train_tpu.utils import xplane  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_perf_state():
    get_registry().reset()
    perf_lib._reset_for_tests()
    yield
    get_registry().reset()
    perf_lib._reset_for_tests()


def _write_image_folder(root, n_per_class=3, classes=("a", "b"),
                        size=24):
    from PIL import Image

    rng = np.random.default_rng(0)
    for c in classes:
        d = os.path.join(root, c)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.integers(0, 256, (size, size, 3), np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{i}.jpg"),
                                      "JPEG")


# ------------------------------------------------------ op classification
def test_op_class_classification():
    cases = {
        "%dot.5": "matmul",
        "einsum.fused": "matmul",
        "convolution.12": "conv",
        "custom-call.flash_fwd": "attention",
        "fusion.attn_softmax": "attention",
        "all-reduce.1": "collective",
        "reduce-scatter.3": "collective",
        "infeed.2": "infeed",
        "copy.7": "infeed",
        "fusion.1234": "elementwise",
        "broadcast.9": "elementwise",
        "zzz-unknown-op": "other",
    }
    for name, want in cases.items():
        assert xplane.classify_op_class(name) == want, name
    # the op classes are the closed vocabulary the gauges label by
    for name in cases.values():
        assert name in xplane.PERF_OP_CLASSES + ("other",)


def test_opclass_split_aggregates_ms():
    ops = [("dot.1", 10.0, 2), ("fusion.2", 5.0, 4),
           ("convolution.3", 7.5, 1), ("copy.1", 0.0, 1)]
    split = xplane.opclass_split(ops)
    assert split == {"matmul": 10.0, "conv": 7.5, "elementwise": 5.0}
    assert "infeed" not in split  # zero classes dropped


# ------------------------------------------------------------ stage timers
def test_stage_timer_accumulates_and_splits():
    with perf_lib.stage("decode"):
        time.sleep(0.02)
    with perf_lib.stage("augment"):
        time.sleep(0.005)
    stats = perf_lib.get_input_stats()
    assert stats.seconds["decode"] > stats.seconds["augment"] > 0
    split = stats.split()
    assert abs(sum(split.values()) - 1.0) < 1e-6
    assert stats.top_stage() == "decode"
    # mirrored into the registry counter with the stage label
    assert get_registry().get_value(
        "input_stage_seconds_total", labels={"stage": "decode"}) > 0
    with pytest.raises(KeyError):
        stats.add("not_a_stage", 1.0)


def test_stage_timers_threads_loader(tmp_path):
    from pytorch_distributed_train_tpu.data.datasets import (
        ImageFolderDataset,
    )
    from pytorch_distributed_train_tpu.data.pipeline import HostDataLoader

    _write_image_folder(str(tmp_path))
    ds = ImageFolderDataset(str(tmp_path), image_size=16, train=True)
    loader = HostDataLoader(ds, DataConfig(batch_size=4, num_workers=2),
                            train=True, num_hosts=1, host_id=0)
    batches = list(loader.epoch(0))
    assert batches and batches[0]["image"].shape == (4, 16, 16, 3)
    stats = perf_lib.get_input_stats()
    # the item path times all three host stages
    assert stats.seconds["read"] > 0
    assert stats.seconds["decode"] > 0
    assert stats.seconds["augment"] > 0


def test_stage_timers_grain_loader(tmp_path):
    from pytorch_distributed_train_tpu.data.datasets import (
        ImageFolderDataset,
    )
    from pytorch_distributed_train_tpu.data.grain_pipeline import (
        GrainHostDataLoader,
    )

    _write_image_folder(str(tmp_path))
    ds = ImageFolderDataset(str(tmp_path), image_size=16, train=True)
    loader = GrainHostDataLoader(
        ds, DataConfig(batch_size=4, num_workers=0), train=True,
        num_hosts=1, host_id=0)
    batches = list(loader.epoch(0))
    assert batches and batches[0]["image"].shape == (4, 16, 16, 3)
    stats = perf_lib.get_input_stats()
    # in-process grain runs the instrumented dataset paths inline
    assert stats.seconds["decode"] > 0
    assert stats.seconds["augment"] > 0


def test_h2d_stage_and_prefetch_occupancy(devices8):
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.data.datasets import (
        synthetic_images,
    )
    from pytorch_distributed_train_tpu.data.pipeline import (
        build_input_pipeline,
    )
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(data=-1))
    ds = synthetic_images(64, 8, 10)
    loader, epoch_fn = build_input_pipeline(
        ds, DataConfig(batch_size=8, num_workers=1), mesh, train=True)
    seen = 0
    for _ in epoch_fn(0):
        seen += 1
    assert seen == loader.steps_per_epoch
    stats = perf_lib.get_input_stats()
    assert stats.seconds["h2d"] > 0  # device assembly is timed
    # the occupancy gauge was set by the producer-queue consumer
    occ = get_registry().get_value("input_prefetch_occupancy")
    assert occ is not None and 0.0 <= occ <= 1.0


# ------------------------------------------------- analytic vs AOT flops
@pytest.mark.parametrize("name,kwargs,seq", [
    ("resnet50", dict(num_classes=1000, image_size=96), None),
    ("resnet18", dict(num_classes=1000, image_size=128), None),
    ("vit_b16", dict(num_classes=1000, image_size=96), None),
    ("bert_base", dict(vocab_size=30522, hidden_size=768, num_layers=12,
                       num_heads=12, mlp_dim=3072, max_seq_len=128), 128),
])
def test_analytic_flops_match_aot_cost_analysis(name, kwargs, seq):
    """The drift gate (docs/performance.md): the hand-rolled FLOP
    formulas must agree with XLA's own AOT count within tolerance, so a
    model change can't silently skew every derived MFU number. The
    bound is loose (elementwise ops, stem padding and backend counting
    differences are real) but far tighter than any formula bug: a
    forgotten layer or a 2x MAC/FLOP slip lands well outside it."""
    cfg = ModelConfig(name=name, **kwargs)
    analytic = flops_lib.fwd_flops_per_item(cfg, seq)
    aot = flops_lib.aot_fwd_flops_per_item(cfg, seq=seq)
    assert analytic and aot
    ratio = aot / analytic
    assert 0.75 < ratio < 1.25, (name, ratio)


def test_aot_flops_unlisted_model_is_none():
    cfg = ModelConfig(name="t5", vocab_size=100, hidden_size=8,
                      num_layers=1, num_heads=2, mlp_dim=16)
    assert flops_lib.aot_fwd_flops_per_item(cfg) is None


# ------------------------------------------------------------- the ledger
def _seed_rows(ledger, metric="resnet50_images_per_sec_per_chip",
               values=(2500, 2520, 2480, 2510, 2505), mfu=31.5):
    for v in values:
        ledger.append(metric, v, unit="images/sec/chip",
                      mfu_pct=mfu, source="test")


def test_ledger_append_and_load(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger = perf_lib.PerfLedger(path)
    row = ledger.append("m1", 10.0, unit="u", config={"a": 1},
                        stall_split={"decode": 0.8, "read": 0.2},
                        none_dropped=None)
    assert row["config_digest"] == perf_lib.config_digest({"a": 1})
    assert "none_dropped" not in row
    # torn tail line is skipped, good rows survive
    with open(path, "a") as f:
        f.write('{"metric": "torn"')
    rows = ledger.load()
    assert len(rows) == 1 and rows[0]["metric"] == "m1"
    assert rows[0]["stall_split"]["decode"] == 0.8
    assert get_registry().get_value("perf_ledger_rows_total") == 1.0


@pytest.mark.analysis
def test_ledger_check_passes_on_stable_history(tmp_path):
    ledger = perf_lib.PerfLedger(str(tmp_path / "l.jsonl"))
    _seed_rows(ledger)
    ledger.append("resnet50_images_per_sec_per_chip", 2495,
                  unit="images/sec/chip", mfu_pct=31.4, source="test")
    assert ledger.check() == []


@pytest.mark.analysis
def test_ledger_check_names_seeded_regression(tmp_path):
    """The E2E gate half: a fast history then a slow row — the check
    exits nonzero NAMING the regressed metric, via library and CLI."""
    path = str(tmp_path / "l.jsonl")
    ledger = perf_lib.PerfLedger(path)
    _seed_rows(ledger)
    ledger.append("resnet50_images_per_sec_per_chip", 1200,
                  unit="images/sec/chip", mfu_pct=15.0, source="test")
    regs = ledger.check()
    assert regs, "seeded regression not detected"
    assert {r["key"] for r in regs} == {"value", "mfu_pct"}
    assert all(r["metric"] == "resnet50_images_per_sec_per_chip"
               for r in regs)
    assert get_registry().get_value("perf_regressions_total") == 2.0

    import perf_ledger as perf_ledger_cli

    rc = perf_ledger_cli.main(["--path", path, "--check"])
    assert rc == 1
    # an improvement must NOT gate (the detector is direction-aware)
    ledger2 = perf_lib.PerfLedger(str(path) + ".up")
    _seed_rows(ledger2)
    ledger2.append("resnet50_images_per_sec_per_chip", 4000,
                   unit="images/sec/chip", mfu_pct=50.0, source="test")
    assert ledger2.check() == []


@pytest.mark.analysis
def test_ledger_cli_check_smoke_on_round_history(tmp_path):
    """`--import` then `--check` against a BENCH_r*.json round history
    the test writes itself, in a scratch ledger: the CI smoke — import
    is idempotent and the gate runs clean on a steady trajectory."""
    import perf_ledger as perf_ledger_cli

    repo = tmp_path / "repo"
    repo.mkdir()
    for i, value in enumerate((2490.0, 2510.0, 2500.0, 2530.0, 2520.0), 1):
        path = repo / f"BENCH_r{i:02d}.json"
        path.write_text(json.dumps({"n": i, "rc": 0, "parsed": {
            "metric": "resnet50_images_per_sec_per_chip", "value": value,
            "unit": "images/sec/chip", "mfu_pct": 31.0,
            "platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1}}))
        os.utime(path, (1000.0 * i, 1000.0 * i))
    path = str(tmp_path / "repo.jsonl")
    rc = perf_ledger_cli.main(["--path", path, "--import",
                               "--repo", str(repo)])
    assert rc == 0
    ledger = perf_lib.PerfLedger(path)
    assert len(ledger.load()) == 5
    assert ledger.import_bench_history(str(repo)) == 0  # idempotent
    assert perf_ledger_cli.main(["--path", path, "--check"]) == 0


def test_program_ledger_is_not_the_drivers_file(monkeypatch):
    """PERF_LEDGER.jsonl at the repo root is the driver's record: the
    program's own default ledger has another name."""
    monkeypatch.delenv(perf_lib.ENV_LEDGER, raising=False)
    path = perf_lib.default_ledger_path()
    assert os.path.dirname(path) == REPO
    assert os.path.basename(path) == perf_lib.LEDGER_BASENAME
    assert os.path.basename(path).lower() != "perf_ledger.jsonl"


@pytest.mark.analysis
def test_ledger_check_orders_by_ts_and_scopes_by_config(tmp_path):
    """Review-hardening fixes stay fixed: (1) a back-imported OLD slow
    round (older ts) must not be judged as the newest measurement; (2)
    a config change (different config_digest) starts its own
    trajectory; (3) a newest row missing a gated key must not re-judge
    an older row's value as current."""
    ledger = perf_lib.PerfLedger(str(tmp_path / "l.jsonl"))
    _seed_rows(ledger)
    # an imported historical slow round, stamped BEFORE the live rows
    ledger.append("resnet50_images_per_sec_per_chip", 1200,
                  unit="images/sec/chip", mfu_pct=15.0,
                  source="BENCH_r00.json", ts=1.0)
    assert ledger.check() == []  # newest BY TS is the healthy live row

    # same metric name under a different config digest: its slow row
    # has no history in ITS group, so nothing gates
    ledger.append("resnet50_images_per_sec_per_chip", 900,
                  unit="images/sec/chip", config={"batch": 8},
                  source="test")
    assert ledger.check() == []

    # newest row measures value but not mfu_pct: the old mfu series
    # must not be re-judged; the value series still gates
    ledger2 = perf_lib.PerfLedger(str(tmp_path / "l2.jsonl"))
    _seed_rows(ledger2)
    ledger2.append("resnet50_images_per_sec_per_chip", 1200,
                   unit="images/sec/chip", source="test")  # no mfu_pct
    regs = ledger2.check()
    assert {r["key"] for r in regs} == {"value"}


def test_ledger_import_stamps_file_mtime(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    path = repo / "BENCH_r01.json"
    path.write_text(json.dumps({
        "parsed": {"metric": "m_x", "value": 7.0, "unit": "u"}}))
    os.utime(path, (1000.0, 1000.0))
    ledger = perf_lib.PerfLedger(str(tmp_path / "l.jsonl"))
    assert ledger.import_bench_history(str(repo)) == 1
    assert ledger.load()[0]["ts"] == 1000.0


def test_kernel_gap_ideal_capped_by_compute_share():
    """MFU sample larger than the capture's compute share (shares from
    different steps, approximate classification): per-class gaps stay
    >= 0 and sum to 1 - min(MFU, compute share)."""
    ranked = perf_lib.kernel_gap(50.0, {"matmul": 40.0,
                                        "elementwise": 60.0})
    by_cls = {c: gap for c, _, gap in ranked}
    assert by_cls["matmul"] == 0.0
    assert by_cls["elementwise"] == 0.6
    assert sum(g for _, _, g in ranked) == pytest.approx(0.6, abs=1e-6)


def test_ledger_import_formats(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    (repo / "BENCH_r01.json").write_text(json.dumps({
        "parsed": {"metric": "m_x", "value": 7.0, "unit": "u",
                   "mfu_pct": 30.0}}))
    (repo / "BENCH_r02.json").write_text(json.dumps({
        "parsed": {"metric": None, "value": None,
                   "error": "tpu_unavailable"}}))
    (repo / "BENCH_r03.json").write_text("not json at all")
    ledger = perf_lib.PerfLedger(str(tmp_path / "l.jsonl"))
    assert ledger.import_bench_history(str(repo)) == 1
    rows = ledger.load()
    assert rows[0]["source"] == "BENCH_r01.json"
    assert ledger.import_bench_history(str(repo)) == 0  # idempotent


# -------------------------------------------------- slow-decode E2E blame
def test_slow_decode_blames_decode_in_ledger_row(tmp_path, monkeypatch):
    """Acceptance E2E: an artificially slowed DECODE stage yields a
    ledger row whose stall split blames decode — not augment, not
    read/h2d — through the real dataset instrumentation."""
    from PIL import Image

    from pytorch_distributed_train_tpu.data.datasets import (
        ImageFolderDataset,
    )
    from pytorch_distributed_train_tpu.data.pipeline import HostDataLoader

    _write_image_folder(str(tmp_path / "data"))
    orig_convert = Image.Image.convert

    def slow_convert(self, *args, **kwargs):
        time.sleep(0.01)  # the decode stage, slowed 10ms/image
        return orig_convert(self, *args, **kwargs)

    monkeypatch.setattr(Image.Image, "convert", slow_convert)
    ds = ImageFolderDataset(str(tmp_path / "data"), image_size=16,
                            train=True)
    loader = HostDataLoader(ds, DataConfig(batch_size=4, num_workers=2),
                            train=True, num_hosts=1, host_id=0)
    list(loader.epoch(0))
    stats = perf_lib.get_input_stats()
    assert stats.top_stage() == "decode"
    split = stats.split()
    assert split["decode"] > split.get("augment", 0.0)
    assert split["decode"] > split.get("read", 0.0)

    ledger = perf_lib.PerfLedger(str(tmp_path / "l.jsonl"))
    ledger.append("synthetic_run_images_per_sec", 123.0,
                  unit="images/sec (host)", stall_split=split,
                  source="test")
    row = ledger.load()[-1]
    blamed = max(row["stall_split"], key=row["stall_split"].get)
    assert blamed == "decode"


# ------------------------------------------------------- kernel-gap audit
def test_kernel_gap_math():
    ranked = perf_lib.kernel_gap(
        30.0, {"conv": 50.0, "elementwise": 30.0, "infeed": 20.0})
    by_cls = {c: (share, gap) for c, share, gap in ranked}
    # non-compute classes: whole share is gap
    assert by_cls["elementwise"] == (0.3, 0.3)
    assert by_cls["infeed"] == (0.2, 0.2)
    # compute class: share minus its slice of the ideal time
    assert by_cls["conv"][1] == pytest.approx(0.5 - 0.3, abs=1e-6)
    # gap shares sum to 1 - MFU exactly
    assert sum(g for _, _, g in ranked) == pytest.approx(0.7, abs=1e-3)
    # no op-class data: one unattributed row carrying the whole gap
    assert perf_lib.kernel_gap(40.0, None) == [
        ("unattributed", 1.0, 0.6)]


def test_kernel_gap_report_lists_classes(tmp_path):
    ledger = perf_lib.PerfLedger(str(tmp_path / "l.jsonl"))
    ledger.append("resnet50_images_per_sec_per_chip", 2541.0,
                  unit="images/sec/chip", mfu_pct=31.65,
                  opclass_ms={"conv": 40.0, "elementwise": 12.0,
                              "infeed": 8.0}, source="test")
    report = perf_lib.kernel_gap_report(ledger.load())
    assert "resnet50" in report and "31.65% MFU" in report
    for cls in ("conv", "elementwise", "infeed"):
        assert cls in report
    # presets without rows say so instead of vanishing
    assert "bert_base: no ledger row" in report

    import perf_ledger as perf_ledger_cli

    assert perf_ledger_cli.main(
        ["--path", str(tmp_path / "l.jsonl"), "--audit"]) == 0


# --------------------------------------------------- capture attribution
def test_attribute_capture_without_dump_is_none(tmp_path):
    assert perf_lib.attribute_capture(str(tmp_path)) is None


def test_publish_opclass_and_mfu_gauges():
    perf_lib.record_mfu(31.65)
    perf_lib.publish_opclass_split({"matmul": 12.5, "elementwise": 3.0})
    reg = get_registry()
    assert reg.get_value("perf_mfu_pct") == 31.65
    assert reg.get_value("perf_opclass_ms",
                         labels={"class": "matmul"}) == 12.5
    text = reg.render()
    assert 'perf_opclass_ms{class="matmul"}' in text


# ------------------------------------------------------- report surfaces
def test_obs_report_perf_section():
    import obs_report

    recs = [
        {"tag": "train", "step": 50, "mfu_pct": 31.65},
        {"tag": "summary", "step": 100, "input_stage_s_decode": 8.0,
         "input_stage_s_read": 1.0, "input_stage_s_h2d": 0.5},
    ]
    events = [{"category": "perf", "name": "attribution", "host": "host0",
               "detail": {"opclass_ms": {"conv": 40.0, "infeed": 5.0},
                          "total_ms": 45.0, "plane": "/device:TPU:0"}}]
    lines = obs_report.perf_section(recs, events)
    text = "\n".join(lines)
    assert "31.65% MFU" in text
    assert "decode" in text and "conv" in text
    # quiet line, not a crash, on a pre-perf-plane run
    assert "no attribution records" in "\n".join(
        obs_report.perf_section([{"tag": "train", "step": 1}], []))


def test_timeline_marks_perf_regression_landmark():
    import timeline_report

    assert ("anomaly", "perf_regression") in timeline_report._LANDMARKS
    # the landmark survives middle-eliding in a long timeline
    events = [{"ts": float(i), "host": "host0", "gen": "0", "step": i,
               "category": "lifecycle", "name": "filler", "detail": {}}
              for i in range(100)]
    events[50] = {"ts": 50.0, "host": "host0", "gen": "0", "step": 50,
                  "category": "anomaly", "name": "perf_regression",
                  "detail": {"metric": "m", "key": "value"}}
    lines = timeline_report.timeline_lines(events, width=10)
    assert any("perf_regression" in line for line in lines)


def test_perf_event_category_is_cataloged():
    from pytorch_distributed_train_tpu.obs import events as events_lib

    assert "perf" in events_lib.CATEGORIES
    doc = open(os.path.join(REPO, "docs", "observability.md"),
               encoding="utf-8").read()
    assert "| `perf`" in doc


# ------------------------------------------------------ trainer end-to-end
@pytest.mark.slow
def test_trainer_summary_stages_and_ledger_row(tmp_path):
    """A tiny CPU fit writes: summary input_stage_s_* keys (h2d at
    minimum — synthetic arrays skip read/decode) and one trainer ledger
    row with throughput + goodput_pct."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = TrainConfig()
    cfg.model.name = "resnet18"
    cfg.model.num_classes = 10
    cfg.model.image_size = 8
    cfg.data.dataset = "synthetic_images"
    cfg.data.synthetic_size = 128
    cfg.data.batch_size = 32
    cfg.data.num_workers = 1
    cfg.optim.name = "sgd"
    cfg.optim.schedule = "constant"
    cfg.optim.warmup_steps = 0
    cfg.total_steps = 4
    cfg.checkpoint.dir = str(tmp_path / "ckpt")
    cfg.checkpoint.save_every_steps = 0
    cfg.checkpoint.async_save = False
    cfg.obs.log_every_steps = 1
    t = Trainer(cfg)
    t.fit()
    t.close()

    recs = [json.loads(line) for line in
            open(os.path.join(cfg.checkpoint.dir, "metrics.jsonl"))]
    summary = [r for r in recs if r.get("tag") == "summary"][-1]
    assert summary["input_stage_s_h2d"] > 0

    ledger = perf_lib.PerfLedger(
        os.path.join(cfg.checkpoint.dir, "perf_ledger.jsonl"))
    rows = ledger.load()
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "resnet18_train_images_per_sec_per_chip"
    assert row["value"] > 0
    assert row["source"] == "trainer"
    assert 0 <= row["goodput_pct"] <= 100
    assert row["config_digest"]


# ------------------------------------------------- fusion worklist (ISSUE 14)
def test_fusion_worklist_actionable():
    """--audit --suggest: top-N op-class gaps per preset with config
    digest + measuring capture, each mapped to a concrete repo lever."""
    rows = [
        {"metric": "vit_b16_images_per_sec_per_chip", "value": 700.0,
         "mfu_pct": 40.0, "config_digest": "abc123def456",
         "source": "bench",
         "opclass_ms": {"matmul": 50.0, "elementwise": 30.0,
                        "collective": 15.0, "infeed": 5.0}},
        {"metric": "bert_base_mlm_tokens_per_sec_per_chip",
         "value": 9e4, "mfu_pct": 35.0},  # no capture -> unattributed
    ]
    wl = perf_lib.fusion_worklist(rows, presets=("vit_b16", "bert_base"),
                                  top_n=2)
    by_preset = {}
    for it in wl:
        by_preset.setdefault(it["preset"], []).append(it)
    # vit: elementwise + collective are the top gap classes (matmul's
    # share is mostly ideal time) and carry the digest
    vit_classes = [it["op_class"] for it in by_preset["vit_b16"]]
    assert "elementwise" in vit_classes and "collective" in vit_classes
    for it in by_preset["vit_b16"]:
        assert it["config_digest"] == "abc123def456"
        assert it["gap_share"] > 0
    ew = next(it for it in by_preset["vit_b16"]
              if it["op_class"] == "elementwise")
    assert "fused_epilogue" in ew["suggestion"]
    co = next(it for it in by_preset["vit_b16"]
              if it["op_class"] == "collective")
    assert "overlap_collectives" in co["suggestion"]
    # the capture-less preset still appears, pointing at the profiler
    assert by_preset["bert_base"][0]["op_class"] == "unattributed"
    # entries are sorted most-gap-first across presets
    assert [it["gap_share"] for it in wl] == sorted(
        (it["gap_share"] for it in wl), reverse=True)
    text = perf_lib.fusion_worklist_report(rows,
                                           presets=("vit_b16",), top_n=2)
    assert "fusion worklist" in text and "elementwise" in text
    # empty ledger: a quiet pointer, not a crash
    assert "no audited ledger rows" in perf_lib.fusion_worklist_report([])


def test_perf_ledger_cli_suggest(tmp_path, capsys):
    import perf_ledger as plcli

    path = tmp_path / "ledger.jsonl"
    perf_lib.PerfLedger(str(path)).append(
        "vit_b16_images_per_sec_per_chip", 700.0, mfu_pct=40.0,
        opclass_ms={"matmul": 60.0, "elementwise": 40.0})
    rc = plcli.main(["--path", str(path), "--audit", "--suggest",
                     "--presets", "vit_b16"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "kernel-gap audit" in out
    assert "fusion worklist" in out and "elementwise" in out
    rc = plcli.main(["--path", str(path), "--suggest", "--json",
                     "--presets", "vit_b16"])
    out = capsys.readouterr().out
    assert rc == 0 and '"worklist"' in out


def test_obs_report_renders_worklist():
    import obs_report

    recs = [{"tag": "train", "step": 50, "mfu_pct": 40.0}]
    rows = [{"metric": "vit_b16_images_per_sec_per_chip", "value": 700.0,
             "mfu_pct": 40.0,
             "opclass_ms": {"matmul": 60.0, "elementwise": 40.0}}]
    text = "\n".join(obs_report.perf_section(recs, None, rows))
    assert "worklist:" in text and "elementwise" in text
    # no ledger rows -> no worklist lines, section otherwise intact
    text2 = "\n".join(obs_report.perf_section(recs, None, None))
    assert "worklist:" not in text2


def test_audit_skips_compute_arm_rows():
    """Arm rows (vit_b16_ga4_* / _overlap_ / _fusedep_) own their own
    trajectories — the audit/worklist must pick the CANONICAL preset
    row even when an arm row is newer."""
    rows = [
        {"metric": "vit_b16_images_per_sec_per_chip", "value": 700.0,
         "mfu_pct": 40.0, "opclass_ms": {"matmul": 60.0,
                                         "elementwise": 40.0}},
        {"metric": "vit_b16_ga4_images_per_sec_per_chip", "value": 650.0,
         "mfu_pct": 37.0},
        {"metric": "vit_b16_overlap_images_per_sec_per_chip",
         "value": 710.0, "mfu_pct": 41.0},
    ]
    report = perf_lib.kernel_gap_report(rows, presets=("vit_b16",))
    assert "@ 40.00% MFU" in report  # the canonical row, not the arms
    wl = perf_lib.fusion_worklist(rows, presets=("vit_b16",), top_n=1)
    assert wl and wl[0]["metric"] == "vit_b16_images_per_sec_per_chip"
