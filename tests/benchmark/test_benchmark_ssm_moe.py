"""The ``granite4h_window_saturated`` cell's files: the manifest resolves it
with its configuration, deployment, reference and every metric file; the
configuration keeps every number of the catalog's row but the cut; a whole
run of its deployment at the small preset on the CPU comes out ``correct``
until the timed path is broken (in the state-space mixer's skip, in the
residual multiplier, in the logits' scale); the cost functions give hand
counts and what ISSUE 44 reckoned; and the scope-based readers give the
numbers worked out from
``benchmark/reduce/fixtures/scoped_ssm_dispatches.textproto`` (scopes
``mamba`` > ``mamba.project`` / ``mamba.conv`` / ``mamba.scan`` /
``mamba.gate``, ``gqa`` > ``gqa.project`` / ``gqa.attend``), and nothing
where a capture has no such scope."""

import ast
import dataclasses
import gc
import json
import os
import shutil

import numpy as np
import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reduce import costs_ssm_moe as costs
from benchmark.reduce import host_spans, scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
FIXTURE = os.path.join(FIXTURES, "scoped_ssm_dispatches.textproto")
CELL = "granite4h_window_saturated"
CONFIG = "kafka_history_granite4h"
OWN_METRICS = ("mamba_roofline.sat", "mamba_device_share.sat",
               "mamba_scan_device_share.sat")
SHARED_METRICS = ("backbone_roofline.sat", "expert_roofline.sat",
                  "moe_device_share.sat", "pairs_per_token.sat",
                  "absent_pairs_per_token.sat",
                  "expert_load_max_over_mean.sat", "fetch_ms.sat",
                  "idle_fetch_pct.sat")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ("num_hidden_layers", "num_local_experts", "vocab_size")
DECIDING = ("mean_abs_dlogit", "choice_rel_diff", "max_abs_dp_own",
            "mean_row_rms_dlogit_slice")
PRINTED = ("max_abs_dp", "max_abs_dlogit_slice", "max_row_rms_dlogit_slice")


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# -- the manifest ------------------------------------------------------------------

@benchmark_manifests.manifest_level
def test_the_manifest_resolves_the_cell_with_every_file_it_names():
    cell = benchmark_manifests.repo_manifest().resolve(CELL)
    assert cell.chips == 1 and cell.deployment_kind == "kafka_history_lm3"
    assert cell.generator_kind == "bus"
    assert cell.config_name == CONFIG
    assert cell.traffic_name == "keyed_window_saturated"
    assert {m.name for m in cell.end_to_end} == {"tx_s", "setup_s"}
    reported = {m.name for m in cell.per_layer}
    assert set(OWN_METRICS) | set(SHARED_METRICS) <= reported
    assert {"device_idle.sat", "idle_wait_pct.sat", "dispatch_ms.sat",
            "router_service_us.sat", "idle_starved_pct.sat",
            "period_ms.sat", "worker_gap_ms.sat", "handoff_ms.sat",
            "loop_await_ms.sat", "loop_unowned_ms.sat"} <= reported
    # what is another model's alone stays away
    assert not reported & {
        "kda_roofline.sat", "kernel_roofline.sat", "cca_roofline.sat",
        "cca_device_share.sat", "skip_share.sat", "router_device_share.sat",
        "gather_offcpu_pct.sat", "mla_roofline.sat", "mla_device_share.sat",
        "hc_roofline.sat", "hc_device_share.sat"}
    for m in cell.per_layer:  # every reader a metric's file names is there
        manifest.load_kind("readers", cell.metric_docs[m.name]["reader"])
    manifest.load_kind("deployments", cell.deployment_kind)
    ref = manifest.load_kind("reference", cell.config["reference"]["module"])
    for name in ("make_params", "preload_rows", "sampled", "aux_path",
                 "served_and_expected", "compare", "miss_controls"):
        assert callable(getattr(ref, name))
    assert set(cell.config["reference"]["limits"]) == set(DECIDING) == set(
        cell.config["reference"]["limits_why"])
    # no widest gap decides: each swings with the seed (PR 35)
    assert not set(PRINTED) & set(cell.config["reference"]["limits"])
    assert manifest.load_kind("reduce", "costs_" + cell.config["costs"][
        "kind"]).PARTS == ("mamba", "gqa", "experts")


@pytest.mark.parametrize("other", [
    "ling3_window_saturated", "zaya1_window_saturated",
    "mistral4_window_saturated", "xing4_window_saturated",
    "history_saturated"])
@benchmark_manifests.manifest_level
def test_the_new_metrics_are_reported_in_the_new_cell_alone(other):
    theirs = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        other).per_layer}
    assert not theirs & set(OWN_METRICS)


@benchmark_manifests.manifest_level
def test_the_cell_and_its_entries_are_in_the_manifest():
    """Found by name, nothing counted: the configuration, the cell of one
    chip, its three metrics under the mixers' layer, and its name in the
    list of every metric it reports."""
    doc = benchmark_manifests.repo_doc()
    entry = {c["name"]: c for c in doc["configs"]}[CONFIG]
    assert entry["reduced"] == [*REDUCED, "table_rows"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "keyed_window_saturated"
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    for name in OWN_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "Backbone mixers"
        assert per_layer[name]["moves"] == "tx_s"
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["unit"] == "%"
    assert per_layer["mamba_roofline.sat"]["better"] == "higher"
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"]
    tx_s = {m["name"]: m for m in doc["end_to_end"]}["tx_s"]
    assert CELL in tx_s["workloads"]


@benchmark_manifests.manifest_level
def test_the_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's ``config`` is in the file with its
    value, but the three counts ``reduced`` lists (depth, experts held,
    vocabulary rows); no width is cut, and ``layer_types`` is whole."""
    c = _real_config()
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    assert {k: c[k] for k in published} == published
    kinds = c["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    assert c["layers_kept"] == list(range(10))  # one whole period
    assert (c["num_hidden_layers"], c["num_local_experts"],
            c["vocab_size"]) == (10, 36, 50176)
    assert c["published"] == {"num_hidden_layers": 40,
                              "num_local_experts": 72, "vocab_size": 100352}
    assert set(c["reduced"]) == {*REDUCED, "table_rows"}
    assert c["experts_held"] == {"first": 0, "count": 36}
    assert c["num_experts_routed_over"] == 72
    assert c["layer_stack"] in ("scanned", "listed")
    assert c["scan_chunk"] in (64, 128, 384, 640)
    assert "2 chips share each layer" in c["deployment_shape"]
    assert "pipeline stages" in c["deployment_shape"]
    assert "1,067" in c["deployment_shape"]  # how near the experts' load is
    for key in ("tokens", "readout", "expert_width", "router", "mamba",
                "scan_chunk", "positions", "precision", "weights",
                "layer_stack", "length", "max_customers", "left_out",
                "max_batch"):
        assert c["assumed"][key], key
    assert c["serving"] == {"length": 64, "batch_sizes": [2, 4],
                            "compute_dtype": "bfloat16",
                            "max_customers": 131072, "inflight": 2}
    assert c["router"]["max_batch"] == 4
    assert c["preload"] == {"customers": 100000, "records": 64}
    assert any("served + absent = 10 x routed tokens x 10 expert layers"
               in g for g in c["guarantees"])
    assert c["costs"]["kind"] == "ssm_moe"
    assert c["reference"]["module"] == "ssm_moe_f32"
    entry = [e for e in benchmark_manifests.repo_doc()["configs"]
             if e["name"] == CONFIG][0]
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small"][0]
        assert c["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert key in REDUCED or c[key] == value, key


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "ssm_moe_f32.py")) as f:
        source = f.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "math", "time", "zlib",
                        "numpy", "jax", "benchmark"}, imported
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(\n        one_token" in source  # the recurrence
    assert "cumsum" not in source  # no running sums of decays, no chunks
    for symbol in ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
                   "mamba_n_groups", "mamba_d_conv", "mamba_expand",
                   "mamba_conv_bias", "mamba_proj_bias", "mamba_chunk_size",
                   "layer_types", "attention_multiplier",
                   "embedding_multiplier", "residual_multiplier",
                   "logits_scaling", "position_embedding_type",
                   "shared_intermediate_size", "num_experts_per_tok",
                   "tie_word_embeddings"):
        assert symbol in source  # the equations name their keys


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``granite4h_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "granite4h_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("granite4h_window_small")
    cell.traffic["keys"] = dict(cell.traffic["keys"], customers=300)
    cell.traffic["warm_records"] = 16
    cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                    max_backlog=64, batch_records=16)
    return cell


def _drop_the_skip(dep):
    """The timed path broken in the state-space mixer: D = 0, so y_t = S_t
    C_t without D x_t."""
    params = dict(dep.scorer.params)
    params["layers"] = [
        dict(p, mixer=dict(p["mixer"], d=p["mixer"]["d"] * 0.0))
        if "d" in p["mixer"] else p for p in params["layers"]]
    dep.scorer.params = params


def _plain_residuals(dep):
    """The timed path broken in the residual path: x + f(x) in place of x
    + 0.22 f(x). The program's rule is replaced, its compiled programs
    dropped and warmed again (nothing may compile in the window)."""
    from ccfd_tpu.models import hybrid_moe as hm

    dep.undo = (hm.RESIDUALS, "multiplied", hm.RESIDUALS["multiplied"])
    hm.RESIDUALS["multiplied"] = hm.RESIDUALS["plain"]
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


def _unscaled_logits(dep):
    """The timed path broken at the read-out: the logits without
    ``logits_scaling`` under them."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm.slice_logits
    dep.undo = (hm, "slice_logits", kept)

    def unscaled(params, x, cfg, dtype=None):
        return kept(params, x, dataclasses.replace(cfg, logit_divisor=1.0),
                    dtype)

    hm.slice_logits = unscaled
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_drop_the_skip, False, False, ("dlogit", "abs_dp", "choice_rel_diff")),
    (_plain_residuals, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_unscaled_logits, False, False, ("dlogit", "abs_dp")),
    (None, True, False, ("dlogit", "abs_dp", "choice_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment finds family, settings and
    reference by the configuration's names, preloads every ring through
    ``HistoryStore.restore``, counts the pairs served and the other chip's,
    and the comparison follows the path under it. The control (matrices at
    fp8's 3 mantissa bits) comes out not correct on the compared numbers
    alone."""
    cell = _small_cell(str(tmp_path))
    held = {}

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 44, seconds=3.0,
                               trace=False, t_start=0.0, root=ROOT,
                               sabotage=wrapped, control=control)
    finally:
        undo = getattr(held.get("dep"), "undo", None)
        if undo is not None:
            from ccfd_tpu.models import hybrid_moe as hm

            if isinstance(undo[0], dict):
                undo[0][undo[1]] = undo[2]
            else:
                setattr(*undo)
            hm.apply_serving.clear_cache()
    printed = capsys.readouterr().out
    assert result["correct"] is want, printed
    assert set(result["metrics"]) == {"tx_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "CHECK pairs_routed_minus_served: 0.0 == 0 -> ok" in printed
    assert "CHECK served_plus_absent_minus_chosen: 0.0 == 0 -> ok" in printed
    assert "CHECK customers_in_store_minus_preloaded: 0 == 0 -> ok" in printed
    assert "CHECK served_model: 'hybrid_moe' == 'hybrid_moe' -> ok" in printed
    for line in (*(f"INFO compared {name}: " for name in PRINTED),
                 "INFO miss_control rolled: mean_abs_dlogit ",
                 "INFO miss_control proba_rolled: mean_abs_dlogit "):
        assert line in printed
    assert {"rows_compared", *DECIDING} <= set(result["compared"])
    assert not set(PRINTED) & set(result["compared"])
    failed = [line for line in printed.splitlines() if line.endswith("FAIL")]
    if want:
        assert not failed
        # the family's gauge: the deployment's registry holds the lowest
        # running log-decay of the run, no reference asked; and the other
        # chip's pairs were counted
        dep = held["dep"]
        assert dep.registry.gauge("lm_ssm_log_decay_min").value() < -1
        assert dep.registry.counter("moe_pairs_absent_total").total() > 0
        assert all(g["scan_chunk"] == 32
                   for g in dep.scorer.executable_grid()["grid"])
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- the chip readings the limits were set from ------------------------------------------

with open(os.path.join(HERE, "granite4h_limit_readings.json")) as _f:
    READINGS = json.load(_f)
SERVED_SEEDS = {run["seed"] for run in READINGS["served"]}


@pytest.mark.parametrize("kind", ["served", "control", "rolled",
                                  "proba_rolled"])
def test_the_chip_readings_the_limits_were_set_from_still_decide_alike(kind):
    """PR 44's runs of ``granite4h_window_saturated`` on the chip: every
    served run passes every limit as the file sets it, every control run
    fails the numbers that see precision (``control_fails``), and each
    ``miss_control`` of a served run fails the number that is there for
    it. A later edit of a limit meets them."""
    limits = _real_config()["reference"]["limits"]
    runs = READINGS[kind]
    assert len(runs) >= (12 if kind != "control" else 4)
    must = set(READINGS["control_fails"])
    assert {"mean_abs_dlogit", "choice_rel_diff"} <= must
    for run in runs:
        over = {name for name in DECIDING if run[name] > limits[name]}
        if kind == "served":
            assert not over, run
        elif kind == "control":
            assert must <= over and "max_abs_dp_own" not in over, run
        elif kind == "rolled":
            assert {"mean_row_rms_dlogit_slice", "mean_abs_dlogit",
                    "choice_rel_diff"} <= over, run
            assert "max_abs_dp_own" not in over, run
        elif run["seed"] in SERVED_SEEDS:
            assert over == {"max_abs_dp_own"}, run
        else:  # a control run's verdicts handed on: that number, too
            assert "max_abs_dp_own" in over, run


@pytest.mark.parametrize("name,low,high,room", [
    # between the served largest and the control's smallest, a factor and a
    # half on both sides at the least
    ("mean_abs_dlogit", "served", "control", 1.5),
    ("choice_rel_diff", "served", "control", 1.5),
    # against a misplaced answer: twofold above the served, threefold below
    # the miss_control
    ("mean_row_rms_dlogit_slice", "served", "rolled", 2.0),
    ("max_abs_dp_own", "served", "proba_rolled", 3.0)])
def test_a_limit_lies_between_its_two_readings_with_room(name, low, high,
                                                         room):
    limit = _real_config()["reference"]["limits"][name]
    assert limit >= room * max(run[name] for run in READINGS[low])
    assert limit <= min(run[name] for run in READINGS[high]) / room


def test_no_widest_gap_decides():
    """``max_abs_dp``, ``max_abs_dlogit_slice`` and
    ``max_row_rms_dlogit_slice`` are in every reading and in no limit."""
    for run in READINGS["served"] + READINGS["control"]:
        assert set(PRINTED) <= set(run)
    assert not set(PRINTED) & set(_real_config()["reference"]["limits"])


# -- costs: hand counts at a small shape ------------------------------------------------

TOY = {
    "hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
    "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 3,
    "mamba_n_groups": 2, "mamba_d_conv": 4,
    "num_experts_routed_over": 6, "intermediate_size": 5,
    "shared_intermediate_size": 7, "experts_held": {"first": 0, "count": 3},
    "vocab_size": 50,
    "layer_types": ["mamba", "attention", "mamba", "mamba", "attention"],
    "layers_kept": [0, 1, 2],
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 55,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one Mamba-2 mixer: inner 16, convolution channels 16 + 2 * 2 * 3 = 28;
    # weights 8 * (16 + 28 + 4) + 16 * 8 = 512; a token 2 * 512 + 2 * 4 * 28
    # (taps) + 4 heads * 5 * 4 * 3 (the recurrence) = 1,488; bytes 2
    # dispatches * 512 * 2 + 30 tokens * 8 * 8; two of the three kept layers
    ("mamba", 2 * 30 * 1488.0, 2 * (2 * 512 * 2 + 30 * 64.0)),
    # the attention layer: heads of 4; q and o 2 * 8 * 8, k and v 2 * 8 * 4:
    # 192; a row's attention 2 heads * 55 pairs * 2 * 2 * 4 = 1,760
    ("gqa", 30 * 384.0 + 3 * 1760.0, 2 * 192 * 2 + 30 * 64.0),
    # experts: 55 pairs * 2 * 3 * 8 * 5; bytes: 3 layers * (2 dispatches * 3
    # held * 120 values * 2 + 30 tokens * 64)
    ("experts", 55 * 240.0, 3 * (2 * 3 * 120 * 2 + 30 * 64.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # a layer's router 8 * 6 and shared expert 3 * 8 * 7: 216, three of
    # them; the tied head 2 * 8 * 50 a row, read once a dispatch
    rest_flop = 3 * 30 * 2 * 216.0 + 3 * 800.0
    rest_moved = (3 * 2 * 216 * 2 + 2 * 8 * 50 * 2 + 30 * (4 + 16.0)
                  + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """A token and layer: the Mamba-2 mixer 137.4 + 67.1 MFLOP of
    projections + 5.2 of recurrence (and 0.07 of convolution): 209.8; the
    attention layer 83.9 + 15.7 of causal scores and mix; the shared expert
    37.7, five held pairs 94.4, the router 0.6: 3.3 GFLOP over ten layers,
    25.5 TFLOP a dispatch of 4 windows. The count does not move with the
    chunk the program serves."""
    c = _real_config()
    tokens = 7680
    work = {"dispatches": 1, "rows": 4, "tokens": tokens,
            "pairs": tokens * 5 * 10, "tokens_per_row": 1920}
    flop, moved = costs.part(c, work, "mamba")
    weights = 4096 * 16768 + 8192 * 4096  # 68.68 M + 33.55 M
    assert flop / (tokens * 9) == 2.0 * weights + 2.0 * 4 * 8448 + (
        128 * 5.0 * 64 * 128)
    assert 209.7e6 < flop / (tokens * 9) < 209.9e6
    assert moved == 9 * (weights * 2 + tokens * 4096 * 8.0)
    for chunk in (64, 640):
        assert costs.part(dict(c, scan_chunk=chunk), work, "mamba") == (
            flop, moved)
    flop, _ = costs.part(c, work, "gqa")
    assert 99.5e6 < flop / tokens < 99.7e6  # 83.9 + 15.7
    assert costs.part(c, work, "experts")[0] / (tokens * 10) == (
        5 * 6.0 * 4096 * 768)
    rest_flop, _ = costs.rest(c, work)
    assert (rest_flop - 4 * 2.0 * 4096 * 50176) / (tokens * 10) == 2.0 * (
        4096 * 72 + 3 * 4096 * 1536)
    flop, moved = costs.backbone(c, work)
    assert 25.4e12 < flop < 25.8e12
    # the weights are read once a dispatch: 9.51 GB, the tied embedding as
    # the head
    held = 9 * weights + 41943040 + 10 * (4096 * 72 + 3 * 4096 * 1536
                                          + 36 * 3 * 4096 * 768)
    assert 9.0e9 < held * 2 + 4096 * 50176 * 2 < 9.6e9
    rows = 20 * tokens * 4096 * 8.0  # sublayers in and out
    assert moved == held * 2 + 4096 * 50176 * 2 + rows + tokens * (
        4 + 8192) + 4 * 50176 * 4.0


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
    # 445 us a program: the state's scan of 30 us is its body's, counted
    # once
    assert cap.busy_s == pytest.approx(890e-6)
    assert cap.seconds_under(["mamba"]) == pytest.approx(460e-6)
    assert cap.seconds_under(["mamba.project"]) == pytest.approx(190e-6)
    assert cap.seconds_under(["mamba.conv"]) == pytest.approx(40e-6)
    assert cap.seconds_under(["mamba.scan"]) == pytest.approx(200e-6)
    assert cap.seconds_under(["mamba.gate"]) == pytest.approx(30e-6)
    assert cap.seconds_under(["gqa"]) == pytest.approx(130e-6)
    assert cap.seconds_under(["gqa.attend"]) == pytest.approx(80e-6)
    assert cap.seconds_under(["moe."]) == pytest.approx(260e-6)
    assert cap.seconds_under(["moe.experts"]) == pytest.approx(180e-6)
    assert scopes.work(OBS) == {
        "dispatches": 2, "rows": 8.0, "tokens": 15360.0,
        "pairs": 768000.0, "tokens_per_row": 1920}
    spans = host_spans.of(OBS)
    assert [e.stats["ssm_log_decay_min"] for e in spans.named(
        "seq.wait")] == [-412.5, -388.25]
    assert [e.stats["pairs_absent"] for e in spans.named("seq.wait")] == [
        384000, 384000]
    for e in spans.named("seq.enqueue"):
        assert (e.stats["attn_kernel"], e.stats["expert_kernel"],
                e.stats["scan_chunk"]) == (1, 1, 128)


@pytest.mark.parametrize("metric,want", [
    ("mamba_device_share.sat", 100 * 460 / 890),
    ("mamba_scan_device_share.sat", 100 * 270 / 890),
    ("moe_device_share.sat", 100 * 260 / 890)])
def test_a_device_share_is_the_scopes_share_of_busy_time(metric, want):
    assert _read(metric, OBS) == pytest.approx(want)


@pytest.mark.parametrize("metric,part,scope_us", [
    ("mamba_roofline.sat", "mamba", 460),
    ("expert_roofline.sat", "experts", 180),
    ("backbone_roofline.sat", "backbone", 890)])
def test_a_roofline_share_is_cost_over_the_scopes_time(
        monkeypatch, metric, part, scope_us):
    """The recorded times are nobody's measurement, so the share comes out
    far over 100% and ``roofline_share`` refuses it: the test takes the
    refusal away and holds the arithmetic, and that the costs are the ones
    the configuration's ``costs.kind`` names (``ssm_moe``)."""
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


def test_the_mixers_floor_is_bound_by_compute():
    """At the published widths a Mamba-2 mixer is its two projections: its
    operations over the chip's peak are some ten times its bytes over the
    chip's bandwidth at 7,680 tokens a dispatch."""
    from benchmark.reduce import trace

    flop, moved = costs.part(OBS["config"], scopes.work(OBS), "mamba")
    share, bound = trace.roofline_share(
        flop, moved, 1.0, "TPU v5 lite", n_devices=1,
        flop_peak="bf16_flop_s")
    assert bound == "compute" and 0 < share < 100
    assert (flop / 197e12) > 5 * (moved / 819e9)


@pytest.mark.parametrize("metric,capture", [
    # an older commit: no scope on any operation, no counts in seq.wait
    *((m, "worker_and_loop.textproto") for m in OWN_METRICS),
    *((m, "/nonexistent") for m in OWN_METRICS),
    # the family's other models: programs, counts and scopes, none named so
    *((m, "scoped_mla_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_cca_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_mhc_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_dispatches.textproto") for m in OWN_METRICS)])
def test_a_capture_without_the_scope_gives_nothing(metric, capture):
    """The parent under this benchmark, and the accepted cells' programs:
    the reader returns None and does not raise."""
    path = capture if capture.startswith("/") else os.path.join(
        FIXTURES, capture)
    assert _read(metric, dict(OBS, capture=path)) is None


def test_the_cost_file_imports_nothing():
    """From the program it takes nothing, nor from anything else."""
    with open(os.path.join(ROOT, "benchmark", "reduce",
                           "costs_ssm_moe.py")) as f:
        tree = ast.parse(f.read())
    imported = {a.name for node in ast.walk(tree)
                if isinstance(node, ast.Import) for a in node.names} | {
        node.module for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)}
    assert imported == {"__future__"}
    assert np.isfinite(costs.backbone(TOY, WORK)[0])
