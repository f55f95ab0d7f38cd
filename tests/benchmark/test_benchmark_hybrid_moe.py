"""The ``ling3_window_saturated`` cell's files: the manifest resolves it,
a whole run of its deployment at the small preset on the CPU comes out
``correct`` until the timed path is broken, the cost functions give hand
counts, and the scope-based readers give the numbers worked out by hand
from ``benchmark/reduce/fixtures/scoped_dispatches.textproto``."""

import ast
import gc
import json
import os
import shutil

import numpy as np
import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reduce import costs_hybrid_moe as costs
from benchmark.reduce import scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(ROOT, "benchmark", "reduce", "fixtures",
                       "scoped_dispatches.textproto")
CELL = "ling3_window_saturated"


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kafka_history_ling3.json")) as f:
        return json.load(f)


# -- the manifest ------------------------------------------------------------------

@benchmark_manifests.manifest_level
def test_the_manifest_resolves_the_cell_with_every_file_it_names():
    cell = benchmark_manifests.repo_manifest().resolve(CELL)
    assert cell.chips == 1 and cell.deployment_kind == "kafka_history_lm"
    assert cell.generator_kind == "bus"
    assert {m.name for m in cell.end_to_end} == {"tx_s", "setup_s"}
    reported = {m.name for m in cell.per_layer}
    assert {"backbone_roofline.sat", "expert_roofline.sat",
            "kda_roofline.sat", "mla_roofline.sat", "moe_device_share.sat",
            "pairs_per_token.sat", "expert_load_max_over_mean.sat",
            "device_idle.sat", "idle_wait_pct.sat", "period_ms.sat",
            "fetch_ms.sat", "idle_fetch_pct.sat"} <= reported
    # what is another model's alone, or reads nothing here, stays away
    assert not reported & {"kernel_roofline.sat", "cca_roofline.sat",
                           "cca_device_share.sat", "skip_share.sat",
                           "router_device_share.sat", "mla_device_share.sat",
                           "absent_pairs_per_token.sat",
                           "gather_offcpu_pct.sat"}
    for m in cell.per_layer:  # every reader a metric's file names is there
        manifest.load_kind("readers", cell.metric_docs[m.name]["reader"])
    for kind in ("deployments", "reference"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", kind,
            {"deployments": "kafka_history_lm.py",
             "reference": "hybrid_moe_f32.py"}[kind]))


def test_the_configuration_holds_every_published_width():
    """Every number of the catalog's ``config`` is in the file under the
    same key, but the three keys the cut lists under ``reduced``."""
    c = _real_config()
    published = {
        "hidden_size": 2560, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "head_dim": 128, "n_group": 8, "topk_group": 4,
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "first_k_dense_replace": 2, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "routed_scaling_factor": 2.5,
        "rope_theta": 6000000, "num_experts_routed_over": 512}
    assert {k: c[k] for k in published} == published
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        7, 128, 39296)
    assert c["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                              "vocab_size": 157184}
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts",
                                 "vocab_size", "table_rows"}
    assert len(c["layers_kept"]) == c["num_hidden_layers"]
    assert costs.layer_kinds(c) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("kda", "moe"),
        ("mla", "moe"), ("kda", "moe"), ("kda", "moe")]
    assert "4 chips share each layer" in c["deployment_shape"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "hybrid_moe_f32.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "dataclasses", "functools", "math",
                        "os", "time", "numpy", "jax", "benchmark"}, imported


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``ling3_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "ling3_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("ling3_window_small")
    cell.traffic["keys"] = dict(cell.traffic["keys"], customers=300)
    cell.traffic["warm_records"] = 16
    cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                    max_backlog=64, batch_records=16)
    return cell


def _zero_an_expert(dep):
    """The timed path broken in the expert layer: one held expert's
    down-projection is zeros in every layer."""
    params = dict(dep.scorer.params)
    layers = []
    for layer in params["layers"]:
        if "experts" in layer["ffn"]:
            ex = layer["ffn"]["experts"]
            layer = dict(layer, ffn=dict(layer["ffn"], experts=dict(
                ex, down=ex["down"].at[0].set(0))))
        layers.append(layer)
    dep.scorer.params = dict(params, layers=layers)


def _drop_the_causal_mask(dep):
    """The timed path broken in MLA: a query reads every real key, the
    ones after it too. The program's mask helper is replaced, its compiled
    programs dropped and warmed again (nothing may compile in the window)."""
    from ccfd_tpu.models import hybrid_moe as hm

    dep.undo = (hm, hm._attendable)
    hm._attendable = lambda real_keys, lo, hi: np.ones(
        (hi - lo, 1), bool) & real_keys[None, :]
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_zero_an_expert, False, False, ("dlogit", "abs_dp", "pairs_rel_diff")),
    (_drop_the_causal_mask, False, False,
     ("dlogit", "abs_dp", "pairs_rel_diff")),
    (None, True, False, ("dlogit", "abs_dp", "pairs_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment preloads every ring through
    ``HistoryStore.restore``, serves by the registry's name, and the
    comparison follows the path under it. The control (matrices at fp8's 3
    mantissa bits) comes out not correct on the compared numbers alone."""
    cell = _small_cell(str(tmp_path))
    held = {}

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 23, seconds=3.0,
                               trace=False, t_start=0.0, root=ROOT,
                               sabotage=wrapped, control=control)
    finally:
        undo = getattr(held.get("dep"), "undo", None)
        if undo is not None:
            setattr(undo[0], "_attendable", undo[1])
            undo[0].apply_serving.clear_cache()
    printed = capsys.readouterr().out
    assert result["correct"] is want, printed
    assert set(result["metrics"]) == {"tx_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "CHECK pairs_routed_minus_served: 0.0 == 0 -> ok" in printed
    assert "CHECK customers_in_store_minus_preloaded: 0 == 0 -> ok" in printed
    # the comparison's own controls, printed with this reference's numbers
    for line in ("INFO miss_control rolled: mean_abs_dlogit ",
                 "INFO miss_control proba_rolled: mean_abs_dlogit "):
        assert line in printed
    failed = [line for line in printed.splitlines() if line.endswith("FAIL")]
    if want:
        assert not failed
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- costs: hand counts at a small shape ------------------------------------------------

TOY = {
    "hidden_size": 8, "num_attention_heads": 2, "head_dim": 4,
    "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
    "kv_lora_rank": 3, "short_conv_kernel_size": 4, "intermediate_size": 10,
    "moe_intermediate_size": 5, "moe_shared_expert_intermediate_size": 6,
    "num_experts_routed_over": 16, "experts_held": {"first": 0, "count": 4},
    "vocab_size": 50, "layer_group_size": 2, "first_k_dense_replace": 1,
    "layers_kept": [0, 1, 2],  # KDA + dense, MLA + experts, KDA + experts
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 70,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one KDA mixer: weights 4 * 8 * 8 + 2 * 8 * 2 + 8 * 8 = 352; a token:
    # 2 * 352 + 3 * 2 * 4 * 8 taps + 2 heads * 7 * 16 = 704 + 192 + 224;
    # two such layers; bytes: 2 dispatches * 352 * 2 + 30 * 8 * 8 each
    ("kda", 2 * 30 * 1120.0, 2 * (2 * 352 * 2 + 30 * 64.0)),
    # MLA: weights 8 * 2 * 6 + 8 * 5 + 3 * 2 * 8 + 2 * 4 * 8 = 248; a row's
    # attention 2 heads * 55 pairs * 2 * (4 + 2 + 4) = 2200
    ("mla", 30 * 2 * 248.0 + 3 * 2200.0, 2 * 248 * 2 + 30 * 64.0),
    # experts: 70 pairs * 2 * 3 * 8 * 5; bytes: 2 expert layers * (2
    # dispatches * 4 held * 120 values * 2 + 30 tokens * 64)
    ("experts", 70 * 240.0, 2 * (2 * 4 * 120 * 2 + 30 * 64.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # rest: dense 3 * 8 * 10 = 240; router + shared 8 * 16 + 3 * 8 * 6 = 272,
    # twice; head 2 * 8 * 50 a row
    rest_flop = 30 * 2 * (240 + 2 * 272.0) + 3 * 800.0
    rest_moved = (2 * (240 + 2 * 272) * 2 + 2 * 8 * 50 * 2
                  + 30 * (4 + 16.0) + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """1.06 GFLOP a token on this chip at 2 pairs a token and layer."""
    c = _real_config()
    work = {"dispatches": 1, "rows": 16, "tokens": 30720,
            "pairs": 30720 * 2 * 6, "tokens_per_row": 1920}
    flop, moved = costs.backbone(c, work)
    assert 1.04e9 < flop / 30720 < 1.08e9
    assert moved > 10.3e9  # every weight once


# -- the scope-based readers on the recorded capture ------------------------------------

OBS = {"capture": FIXTURE, "config": _real_config()}


def _read(metric: str, obs: dict):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        doc = json.load(f)
    return manifest.load_kind("readers", doc["reader"]).read(
        obs, doc["args"])


def test_the_capture_by_scope_gives_the_known_numbers():
    cap = scopes.of(OBS)
    assert cap.programs == 2 and cap.n_devices == 1
    # 350 us a program: the while's 100 us are its body's, counted once;
    # the operation before the first program is no program's
    assert cap.busy_s == pytest.approx(700e-6)
    assert cap.seconds_under(["kda"]) == pytest.approx(200e-6)
    assert cap.seconds_under(["moe.experts"]) == pytest.approx(300e-6)
    assert cap.seconds_under(["moe."]) == pytest.approx(360e-6)
    assert cap.seconds_under(["mla"]) == pytest.approx(80e-6)
    assert cap.op_name_seconds[""] == pytest.approx(40e-6)
    assert scopes.work(OBS) == {
        "dispatches": 2, "rows": 32.0, "tokens": 61440.0,
        "pairs": 780000.0, "tokens_per_row": 1920}


def test_moe_device_share_is_the_share_of_busy_time():
    assert _read("moe_device_share.sat", OBS) == pytest.approx(
        100 * 360 / 700)


@pytest.mark.parametrize("metric,part,scope_us", [
    ("kda_roofline.sat", "kda", 200), ("mla_roofline.sat", "mla", 80),
    ("expert_roofline.sat", "experts", 300),
    ("backbone_roofline.sat", "backbone", 700)])
def test_a_roofline_share_is_cost_over_the_scopes_time(
        monkeypatch, metric, part, scope_us):
    """The recorded times are nobody's measurement (a dispatch takes
    hundreds of milliseconds, not 350 us), so the share comes out far over
    100% and ``roofline_share`` refuses it: the test takes the refusal
    away and holds the arithmetic."""
    import jax

    from benchmark.reduce import trace

    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    seen = {}

    def share(flop, moved, seconds, kind, n_devices=1, flop_peak=""):
        seen.update(flop=flop, moved=moved, seconds=seconds)
        return 50.0, "compute"

    monkeypatch.setattr(trace, "roofline_share", share)
    assert _read(metric, OBS) == 50.0
    work = scopes.work(OBS)
    want = (costs.backbone(OBS["config"], work) if part == "backbone"
            else costs.part(OBS["config"], work, part))
    assert (seen["flop"], seen["moved"]) == want
    assert seen["seconds"] == pytest.approx(scope_us * 1e-6)


@pytest.mark.parametrize("metric", [
    "kda_roofline.sat", "mla_roofline.sat", "expert_roofline.sat",
    "backbone_roofline.sat", "moe_device_share.sat"])
def test_a_capture_of_a_program_without_scopes_gives_nothing(metric):
    """An older commit under this benchmark: no scope on any operation, no
    ``seq.wait`` with counts: the reader returns None and does not raise."""
    bare = dict(OBS, capture=os.path.join(
        ROOT, "benchmark", "reduce", "fixtures", "worker_and_loop.textproto"))
    assert _read(metric, bare) is None
    assert _read(metric, dict(OBS, capture="/nonexistent")) is None


@pytest.mark.parametrize("metric,want", [
    ("pairs_per_token.sat", 1200 / (100 * 6)),
    ("expert_load_max_over_mean.sat", 30.0 / 12)])
def test_the_counter_metrics_read_the_deployments_counters(metric, want):
    before = {"moe_pairs_served_total": 10.0, "moe_routed_token_layers": 60.0,
              "moe_expert_load_ratio_total": 5.0,
              "moe_layer_dispatches_total": 6.0}
    after = {"moe_pairs_served_total": 1210.0,
             "moe_routed_token_layers": 660.0,
             "moe_expert_load_ratio_total": 35.0,
             "moe_layer_dispatches_total": 18.0}
    assert _read(metric, {"before": before, "after": after}) == \
        pytest.approx(want)
    assert _read(metric, {"before": {}, "after": {}}) is None
