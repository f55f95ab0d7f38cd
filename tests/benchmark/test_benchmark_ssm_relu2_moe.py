"""The ``nemotron3n_window_saturated`` cell's files: the manifest resolves
it with its configuration, deployment, reference and every metric file; the
configuration keeps every number of the catalog's row but the cut; a whole
run of its deployment at the small preset on the CPU comes out ``correct``
until the timed path is broken (in the gated norm's groups, in the experts'
body); the cost functions give hand counts and what
ISSUE 49 reckoned (18.6 TFLOP a dispatch of 8 windows, 4.585 B parameters);
and the three metric files this cell brings (``gqa_device_share.sat``,
``gqa_roofline.sat``, ``shared_expert_device_share.sat``) give the numbers
worked out from ``benchmark/reduce/fixtures/scoped_ssm_dispatches.textproto``
(scopes ``gqa`` > ``gqa.project`` / ``gqa.attend``, ``moe.shared``), and
nothing where a capture has no such scope."""

import ast
import gc
import json
import os
import shutil

import numpy as np
import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reduce import costs_ssm_relu2_moe as costs
from benchmark.reduce import scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
FIXTURE = os.path.join(FIXTURES, "scoped_ssm_dispatches.textproto")
CELL = "nemotron3n_window_saturated"
CONFIG = "kafka_history_nemotron3n"
OWN_METRICS = ("gqa_device_share.sat", "gqa_roofline.sat",
               "shared_expert_device_share.sat")
SHARED_METRICS = ("backbone_roofline.sat", "expert_roofline.sat",
                  "moe_device_share.sat", "pairs_per_token.sat",
                  "absent_pairs_per_token.sat",
                  "expert_load_max_over_mean.sat", "fetch_ms.sat",
                  "idle_fetch_pct.sat")
# the other state-space cell's own three: tests/benchmark/test_benchmark_ssm_moe.py
# holds their lists to that cell alone, and a model_config PR edits no
# accepted test, so this cell's ``mamba`` scopes are read in PERF.md
# section 5 from the capture and not in its result line
THEIRS_ALONE = ("mamba_roofline.sat", "mamba_device_share.sat",
                "mamba_scan_device_share.sat")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ("num_hidden_layers", "n_routed_experts", "vocab_size")
DECIDING = ("mean_abs_dlogit", "choice_rel_diff", "max_abs_dp_own",
            "mean_row_rms_dlogit_slice")
PRINTED = ("max_abs_dp", "max_abs_dlogit_slice", "max_row_rms_dlogit_slice")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


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
    # every metric the other state-space cell reports is reported here,
    # but the three its own test holds to it alone
    theirs = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        "granite4h_window_saturated").per_layer}
    assert theirs - reported == set(THEIRS_ALONE)
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
    "granite4h_window_saturated", "history_saturated"])
@benchmark_manifests.manifest_level
def test_the_new_metrics_are_reported_in_the_new_cell_alone(other):
    theirs = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        other).per_layer}
    assert not theirs & set(OWN_METRICS)


@benchmark_manifests.manifest_level
def test_the_cell_and_its_entries_are_in_the_manifest():
    """Found by name, nothing counted: the configuration, the cell of one
    chip, its three metrics under their layers, and its name in the list
    of every metric it shares."""
    doc = benchmark_manifests.repo_doc()
    entry = {c["name"]: c for c in doc["configs"]}[CONFIG]
    assert entry["reduced"] == [*REDUCED, "table_rows"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "keyed_window_saturated"
    assert "720" in cell["why"] and "1,440" in cell["why"]
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    for name, layer in zip(OWN_METRICS, ("Backbone mixers",
                                         "Backbone mixers", "Expert layer")):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == layer
        assert per_layer[name]["moves"] == "tx_s"
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["unit"] == "%"
    assert per_layer["gqa_roofline.sat"]["better"] == "higher"
    assert per_layer["gqa_device_share.sat"]["better"] == "lower"
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"]
    tx_s = {m["name"]: m for m in doc["end_to_end"]}["tx_s"]
    assert CELL in tx_s["workloads"]


@benchmark_manifests.manifest_level
def test_the_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's ``config`` is in the file with its
    value, but the three counts ``reduced`` lists (depth, experts held,
    vocabulary rows); no width is cut, and the pattern is whole."""
    c = _real_config()
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "partial_rotary_factor": 1, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True}
    assert {k: c[k] for k in published} == published
    assert len(PATTERN) == 52 and [PATTERN.count(k) for k in "ME*"] == [
        23, 23, 6]
    assert [i for i, k in enumerate(PATTERN) if k == "*"] == [
        5, 12, 19, 26, 33, 42]
    assert c["layers_kept"] == list(range(14))  # MEMEM*E twice
    assert "".join(PATTERN[i] for i in c["layers_kept"]) == "MEMEM*E" * 2
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (14, 64, 65536)
    assert c["published"] == {"num_hidden_layers": 52,
                              "n_routed_experts": 128, "vocab_size": 131072}
    assert set(c["reduced"]) == {*REDUCED, "table_rows"}
    assert c["experts_held"] == {"first": 0, "count": 64}
    assert c["num_experts_routed_over"] == 128
    assert c["expert_storage_width"] == 1920  # 15 lane tiles; 1,856 = 14.5
    assert c["layer_stack"] in ("scanned", "listed")
    assert 1920 % c["scan_chunk"] == 0
    assert "rms_norm_eps" not in c  # the eps comes through the reader
    assert "4 pipeline stages x 2 chips" in c["deployment_shape"]
    assert "720" in c["deployment_shape"] and "1,440" in c[
        "deployment_shape"]
    for key in ("tokens", "readout", "positions", "head_dim", "mamba",
                "relu2", "expert_keys", "expert_storage_width", "router",
                "norm_eps", "scan_chunk", "precision", "weights",
                "layer_stack", "length", "max_customers", "left_out",
                "max_batch"):
        assert c["assumed"][key], key
    assert "4.585 B" in c["reduced"]["vocab_size"]
    assert c["serving"] == {"length": 64, "batch_sizes": [4, 8],
                            "compute_dtype": "bfloat16",
                            "max_customers": 131072, "inflight": 2}
    assert c["router"]["max_batch"] == 8
    assert c["preload"] == {"customers": 100000, "records": 64}
    assert any("served + absent = 6 x routed tokens x 6 expert layers"
               in g for g in c["guarantees"])
    assert c["costs"]["kind"] == "ssm_relu2_moe"
    assert c["reference"]["module"] == "ssm_relu2_moe_f32"
    entry = [e for e in benchmark_manifests.repo_doc()["configs"]
             if e["name"] == CONFIG][0]
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"][0]
        assert c["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert key in REDUCED or c[key] == value, key


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "ssm_relu2_moe_f32.py")) as f:
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
    for symbol in ("mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                   "n_groups", "conv_kernel", "use_conv_bias",
                   "mamba_proj_bias", "chunk_size", "time_step_min",
                   "time_step_max", "time_step_floor",
                   "hybrid_override_pattern", "head_dim", "norm_eps",
                   "moe_intermediate_size",
                   "moe_shared_expert_intermediate_size",
                   "routed_scaling_factor", "num_experts_per_tok",
                   "norm_topk_prob", "mlp_hidden_act",
                   "tie_word_embeddings", "expert_storage_width"):
        assert symbol in source  # the equations name their keys


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``nemotron3n_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "nemotron3n_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("nemotron3n_window_small")
    cell.traffic["keys"] = dict(cell.traffic["keys"], customers=300)
    cell.traffic["warm_records"] = 16
    cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                    max_backlog=64, batch_records=16)
    return cell


def _rewarmed(dep, holder, name, value):
    """``holder.name`` of the program (a module's attribute, or an entry
    of one of its tables) replaced, its compiled programs dropped and
    warmed again (nothing may compile in the window)."""
    from ccfd_tpu.models import hybrid_moe as hm

    if isinstance(holder, dict):
        dep.undo = (holder, name, holder[name])
        holder[name] = value
    else:
        dep.undo = (holder, name, getattr(holder, name))
        setattr(holder, name, value)
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


def _one_norm_group(dep):
    """The timed path broken in the state-space mixer: the gated norm over
    all the inner values as one group (granite's), not inside each of the
    model's groups."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm._gated_norm
    _rewarmed(dep, hm, "_gated_norm",
              lambda y, gate, weight, eps, groups=1: kept(y, gate, weight,
                                                          eps))


def _relu_without_the_square(dep):
    """The timed path broken in the experts' body, routed and shared:
    W_down relu(u W_up), the square left out."""
    import jax

    from ccfd_tpu.models import hybrid_moe as hm

    def relu(p, x, dtype):
        return hm._mm(jax.nn.relu(hm._mm(x, p["up"], dtype)), p["down"],
                      dtype)

    _rewarmed(dep, hm.EXPERT_BODIES, "relu2", (("up", "down"), relu))


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_one_norm_group, False, False, ("dlogit", "abs_dp", "choice_rel_diff")),
    (_relu_without_the_square, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (None, True, False, ("dlogit", "abs_dp", "choice_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment finds family, settings and
    reference by the configuration's names, preloads every ring through
    ``HistoryStore.restore``, counts the pairs served and the other chip's
    over the three expert layers, and the comparison follows the path
    under it. The control (matrices at fp8's 3 mantissa bits) comes out not
    correct on the compared numbers alone."""
    cell = _small_cell(str(tmp_path))
    held = {}

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 49, seconds=3.0,
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
        dep = held["dep"]
        assert dep.family_config.moe_layers == 3
        assert dep.registry.gauge("lm_ssm_log_decay_min").value() < -1
        assert dep.registry.counter("moe_pairs_absent_total").total() > 0
        grid = dep.scorer.executable_grid()
        assert grid["expert_body"] == "relu2"
        assert all(g["scan_chunk"] == 32 for g in grid["grid"])
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- the chip readings the limits were set from ------------------------------------------

with open(os.path.join(HERE, "nemotron3n_limit_readings.json")) as _f:
    READINGS = json.load(_f)
SERVED_SEEDS = {run["seed"] for run in READINGS["served"]}


@pytest.mark.parametrize("kind", ["served", "control", "rolled",
                                  "proba_rolled"])
def test_the_chip_readings_the_limits_were_set_from_still_decide_alike(kind):
    """PR 49's runs of ``nemotron3n_window_saturated`` on the chip: every
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
    "head_dim": 6, "mamba_num_heads": 4, "mamba_head_dim": 4,
    "ssm_state_size": 3, "n_groups": 2, "conv_kernel": 4,
    "num_experts_routed_over": 6, "moe_intermediate_size": 5,
    "moe_shared_expert_intermediate_size": 7,
    "experts_held": {"first": 0, "count": 3}, "vocab_size": 50,
    "hybrid_override_pattern": "MEM*EMME", "layers_kept": [0, 1, 2, 3, 4],
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 55,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one Mamba-2 mixer: inner 16, convolution channels 16 + 2 * 2 * 3 = 28;
    # weights 8 * (16 + 28 + 4) + 16 * 8 = 512; a token 2 * 512 + 2 * 4 * 28
    # (taps) + 4 heads * 5 * 4 * 3 (the recurrence) = 1,488; bytes 2
    # dispatches * 512 * 2 + 30 tokens * 8 * 8; two M among the five kept
    ("mamba", 2 * 30 * 1488.0, 2 * (2 * 512 * 2 + 30 * 64.0)),
    # the attention layer: heads of head_dim 6 (not 8 / 2); q and o 2 * 8 *
    # 12, k and v 2 * 8 * 6: 288; a row's attention 2 heads * 55 pairs * 2
    # * 2 * 6 = 2,640
    ("gqa", 30 * 576.0 + 3 * 2640.0, 2 * 288 * 2 + 30 * 64.0),
    # experts: 55 pairs * 2 * 2 * 8 * 5 (two matrices); bytes: the 2 expert
    # layers * (2 dispatches * 3 held * 80 values * 2 + 30 tokens * 64)
    ("experts", 55 * 160.0, 2 * (2 * 3 * 80 * 2 + 30 * 64.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # an expert layer's router 8 * 6 and shared expert 2 * 8 * 7: 160, two
    # of them; the untied head 2 * 8 * 50 a row, read once a dispatch
    rest_flop = 2 * 30 * 2 * 160.0 + 3 * 800.0
    rest_moved = (2 * 2 * 160 * 2 + 2 * 8 * 50 * 2 + 30 * (4 + 16.0)
                  + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)
    assert costs.layer_kinds(TOY) == ["mamba", "experts", "mamba", "gqa",
                                      "experts"]


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """A token: a Mamba-2 mixer 55.4 + 22.0 MFLOP of projections + 2.6 of
    recurrence (and 0.05 of convolution): 80.1; an attention layer 47.0 +
    15.7 of causal scores and mix: 62.5; an expert layer the shared expert
    39.9, three held pairs 59.9, the router 0.7: 100.5; 1,208 MFLOP over
    the fourteen sublayers, 18.6 TFLOP a dispatch of 8 windows. The count
    moves neither with the chunk the program serves nor with the width the
    experts are stored at."""
    c = _real_config()
    tokens = 15360
    work = {"dispatches": 1, "rows": 8, "tokens": tokens,
            "pairs": tokens * 3 * 6, "tokens_per_row": 1920}
    flop, moved = costs.part(c, work, "mamba")
    weights = 2688 * 10304 + 4096 * 2688  # 27.70 M + 11.01 M
    assert flop / (tokens * 6) == 2.0 * weights + 2.0 * 4 * 6144 + (
        64 * 5.0 * 64 * 128)
    assert 80.0e6 < flop / (tokens * 6) < 80.2e6
    assert moved == 6 * (weights * 2 + tokens * 2688 * 8.0)
    for change in ({"scan_chunk": 64}, {"scan_chunk": 640},
                   {"expert_storage_width": 1856}):
        assert costs.backbone(dict(c, **change), work) == costs.backbone(
            c, work)
    flop, _ = costs.part(c, work, "gqa")
    assert 62.4e6 < flop / (tokens * 2) < 62.7e6  # 47.0 + 15.7 (rounded)
    assert costs.part(c, work, "experts")[0] / (tokens * 6) == (
        3 * 4.0 * 2688 * 1856)
    rest_flop, _ = costs.rest(c, work)
    assert (rest_flop - 8 * 2.0 * 2688 * 65536) / (tokens * 6) == 2.0 * (
        2688 * 128 + 2 * 2688 * 3712)
    flop, moved = costs.backbone(c, work)
    assert 18.5e12 < flop < 18.7e12
    assert 1208e6 < flop / tokens < 1209e6
    # the weights are read once a dispatch: 4.585 B parameters = 9.17 GB
    # at the published widths, embedding and head among them
    held = (6 * weights + 2 * (2 * 2688 * 4096 + 2 * 2688 * 256)
            + 6 * (2688 * 128 + 2 * 2688 * 3712 + 64 * 2 * 2688 * 1856))
    assert 4.584e9 < held + 2 * 2688 * 65536 < 4.586e9
    rows = 14 * tokens * 2688 * 8.0  # sublayers in and out
    assert moved == held * 2 + 2688 * 65536 * 2 + rows + tokens * (
        4 + 2688 * 2) + 8 * 65536 * 4.0


# -- the three metric files on the recorded capture ----------------------------------------

OBS = {"capture": FIXTURE, "config": _real_config()}


def _read(metric: str, obs: dict):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        doc = json.load(f)
    return manifest.load_kind("readers", doc["reader"]).read(
        obs, doc["args"])


def test_the_capture_by_scope_gives_the_known_numbers():
    cap = scopes.of(OBS)
    assert cap.programs == 2 and cap.busy_s == pytest.approx(890e-6)
    assert cap.seconds_under(["gqa"]) == pytest.approx(130e-6)
    assert cap.seconds_under(["gqa.project"]) == pytest.approx(50e-6)
    assert cap.seconds_under(["gqa.attend"]) == pytest.approx(80e-6)
    assert cap.seconds_under(["moe.shared"]) == pytest.approx(60e-6)


@pytest.mark.parametrize("metric,want", [
    ("gqa_device_share.sat", 100 * 130 / 890),
    ("shared_expert_device_share.sat", 100 * 60 / 890)])
def test_a_device_share_is_the_scopes_share_of_busy_time(metric, want):
    assert _read(metric, OBS) == pytest.approx(want)


def test_the_attention_layers_roofline_share_is_cost_over_the_scopes_time(
        monkeypatch):
    """The recorded times are nobody's measurement, so the share comes out
    far over 100% and ``roofline_share`` refuses it: the test takes the
    refusal away and holds the arithmetic, and that the costs are the ones
    the configuration's ``costs.kind`` names (``ssm_relu2_moe``)."""
    import jax

    from benchmark.reduce import trace

    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    seen = {}

    def share(flop, moved, seconds, kind, n_devices=1, flop_peak=""):
        seen.update(flop=flop, moved=moved, seconds=seconds)
        return 50.0, "compute"

    monkeypatch.setattr(trace, "roofline_share", share)
    assert _read("gqa_roofline.sat", OBS) == 50.0
    want = costs.part(OBS["config"], scopes.work(OBS), "gqa")
    assert (seen["flop"], seen["moved"]) == want
    assert seen["seconds"] == pytest.approx(130e-6)


def test_the_attention_layers_floor_is_bound_by_compute():
    """32 query heads of 128 over 1,920 tokens a row and four projections:
    operations over the chip's peak are several times the bytes over its
    bandwidth at 15,360 tokens a dispatch."""
    from benchmark.reduce import trace

    work = {"dispatches": 1, "rows": 8, "tokens": 15360, "pairs": 0,
            "tokens_per_row": 1920}
    flop, moved = costs.part(OBS["config"], work, "gqa")
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
    *((m, "scoped_cca_dispatches.textproto") for m in OWN_METRICS[:2]),
    *((m, "scoped_mla_dispatches.textproto") for m in OWN_METRICS[:2]),
    *((m, "scoped_mhc_dispatches.textproto") for m in OWN_METRICS[:2]),
    *((m, "scoped_dispatches.textproto") for m in OWN_METRICS[:2]),
    # a model without a shared expert
    ("shared_expert_device_share.sat", "scoped_cca_dispatches.textproto")])
def test_a_capture_without_the_scope_gives_nothing(metric, capture):
    """The parent under this benchmark, and the accepted cells' programs:
    the reader returns None and does not raise."""
    path = capture if capture.startswith("/") else os.path.join(
        FIXTURES, capture)
    assert _read(metric, dict(OBS, capture=path)) is None


def test_the_cost_file_imports_nothing():
    """From the program it takes nothing, nor from anything else."""
    with open(os.path.join(ROOT, "benchmark", "reduce",
                           "costs_ssm_relu2_moe.py")) as f:
        tree = ast.parse(f.read())
    imported = {a.name for node in ast.walk(tree)
                if isinstance(node, ast.Import) for a in node.names} | {
        node.module for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)}
    assert imported == {"__future__"}
    assert np.isfinite(costs.backbone(TOY, WORK)[0])
