"""The ``qwen3next_window_saturated`` cell's files: the manifest resolves
it with its configuration, deployment, reference and every metric file; the
configuration keeps every number of the catalog's row but the cut; a whole
run of its deployment at the small preset on the CPU comes out ``correct``
until the timed path is broken (in the delta rule's decay, in the shared
expert's gate, in the norms' 1 + w); the cost functions give hand counts and
what ISSUE 52 reckoned (0.53 G multiply-adds a token by the matmuls, 5,423 M
parameters); and the three metric files this cell brings
(``gdn_roofline.sat``, ``gdn_device_share.sat``,
``gdn_scan_device_share.sat``) give the numbers worked out from
``benchmark/reduce/fixtures/scoped_gdn_dispatches.textproto`` (scopes
``gdn`` > ``gdn.project`` / ``gdn.conv`` / ``gdn.scan`` / ``gdn.gate``), and
nothing where a capture has no such scope. The lists are held by
membership, never by equality, so that the next configuration with the
layer can share them."""

import ast
import gc
import json
import os
import shutil

import numpy as np
import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reduce import costs_gdn_moe as costs
from benchmark.reduce import scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
FIXTURE = os.path.join(FIXTURES, "scoped_gdn_dispatches.textproto")
CELL = "qwen3next_window_saturated"
CONFIG = "kafka_history_qwen3next"
OWN_METRICS = ("gdn_device_share.sat", "gdn_roofline.sat",
               "gdn_scan_device_share.sat")
SHARED_METRICS = ("backbone_roofline.sat", "expert_roofline.sat",
                  "moe_device_share.sat", "pairs_per_token.sat",
                  "absent_pairs_per_token.sat",
                  "expert_load_max_over_mean.sat", "fetch_ms.sat",
                  "idle_fetch_pct.sat")
# another cell's own: tests/benchmark/test_benchmark_ssm_relu2_moe.py holds
# these three lists to ``nemotron3n_window_saturated`` alone, and a
# model_config PR edits no accepted test, so this cell's ``gqa`` and
# ``moe.shared`` scopes are read in PERF.md section 5 from the capture and
# not in its result line
THEIRS_ALONE = ("gqa_roofline.sat", "gqa_device_share.sat",
                "shared_expert_device_share.sat")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ("num_hidden_layers", "num_experts", "vocab_size")
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
    # every metric the other cell with gqa layers and a shared expert
    # reports is reported here, but the three its own test holds to it alone
    theirs = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        "nemotron3n_window_saturated").per_layer}
    assert theirs - reported == set(THEIRS_ALONE)
    # what is another model's alone stays away
    assert not reported & {
        "kda_roofline.sat", "kernel_roofline.sat", "cca_roofline.sat",
        "cca_device_share.sat", "skip_share.sat", "router_device_share.sat",
        "gather_offcpu_pct.sat", "mla_roofline.sat", "mla_device_share.sat",
        "hc_roofline.sat", "hc_device_share.sat", "mamba_roofline.sat",
        "mamba_device_share.sat", "mamba_scan_device_share.sat"}
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
        "kind"]).PARTS == ("gdn", "gqa", "experts")


@pytest.mark.parametrize("other", [
    "ling3_window_saturated", "zaya1_window_saturated",
    "mistral4_window_saturated", "xing4_window_saturated",
    "granite4h_window_saturated", "nemotron3n_window_saturated",
    "history_saturated"])
@benchmark_manifests.manifest_level
def test_the_new_metrics_are_not_reported_where_no_layer_mixes_so(other):
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
    assert "300" in cell["why"] and "1,200" in cell["why"]
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    for name in OWN_METRICS:  # membership: a later model may share them
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["layer"] == "Backbone mixers"
        assert per_layer[name]["moves"] == "tx_s"
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["unit"] == "%"
    assert per_layer["gdn_roofline.sat"]["better"] == "higher"
    assert per_layer["gdn_device_share.sat"]["better"] == "lower"
    assert per_layer["gdn_scan_device_share.sat"]["better"] == "lower"
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"]
    for name in THEIRS_ALONE:
        assert CELL not in per_layer[name]["workloads"]
    tx_s = {m["name"]: m for m in doc["end_to_end"]}["tx_s"]
    assert CELL in tx_s["workloads"]


@benchmark_manifests.manifest_level
def test_the_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's ``config`` is in the file with its
    value, but the three counts ``reduced`` lists (depth, experts held,
    vocabulary rows); no width is cut."""
    c = _real_config()
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: c[k] for k in published} == published
    layers = c["num_hidden_layers"]
    assert layers in (12, 8)  # three whole periods, or two (the fallback)
    assert c["layers_kept"] == list(range(layers))
    assert (c["num_experts"], c["vocab_size"]) == (128, 37984)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert set(c["reduced"]) == {*REDUCED, "table_rows"}
    assert c["experts_held"] == {"first": 0, "count": 128}
    assert c["num_experts_routed_over"] == 512
    assert c["layer_stack"] in ("scanned", "listed")
    assert c["gdn_chunk"] in (16, 32, 64, 128)
    assert "4 pipeline stages x 4 chips" in c["deployment_shape"]
    assert "300" in c["deployment_shape"] and "1,200" in c[
        "deployment_shape"]
    for key in ("tokens", "readout", "columns", "conv", "l2_norm", "decay",
                "norms", "rotary", "attention_gate", "router",
                "shared_expert", "intermediate_size", "gdn_chunk",
                "precision", "weights", "layer_stack", "length",
                "max_customers", "left_out", "max_batch"):
        assert c["assumed"][key], key
    assert "multi-token-prediction" in c["assumed"]["left_out"]
    assert "5,423 M" in c["reduced"]["vocab_size"]
    assert c["serving"] == {"length": 64, "batch_sizes": [4, 8],
                            "compute_dtype": "bfloat16",
                            "max_customers": 131072, "inflight": 2}
    assert c["router"]["max_batch"] == 8
    assert c["preload"] == {"customers": 100000, "records": 64}
    assert any(f"served + absent = 10 x routed tokens x {layers} expert "
               "layers" in g for g in c["guarantees"])
    assert c["costs"]["kind"] == "gdn_moe"
    assert c["reference"]["module"] == "gdn_moe_f32"
    entry = [e for e in benchmark_manifests.repo_doc()["configs"]
             if e["name"] == CONFIG][0]
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]
        assert c["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert key in REDUCED or c[key] == value, key


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "gdn_moe_f32.py")) as f:
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
    assert "jax.lax.scan(\n        one_token" in source  # a token at a time
    # no running sums of decays, no chunks, no triangular inverse
    assert "cumsum" not in source and "inverse(" not in source
    for symbol in ("linear_num_key_heads", "linear_num_value_heads",
                   "linear_key_head_dim", "linear_value_head_dim",
                   "linear_conv_kernel_dim", "full_attention_interval",
                   "head_dim", "partial_rotary_factor", "rope_theta",
                   "rms_norm_eps", "moe_intermediate_size",
                   "shared_expert_intermediate_size", "num_experts_per_tok",
                   "norm_topk_prob", "decoder_sparse_step",
                   "mlp_only_layers", "tie_word_embeddings"):
        assert symbol in source  # the equations name their keys


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``qwen3next_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "qwen3next_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("qwen3next_window_small")
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


def _no_decay(dep):
    """The timed path broken in the delta rule: every log-decay 0, so the
    state never forgets."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm._gdn_chunk
    _rewarmed(dep, hm, "_gdn_chunk", lambda state, chunk: kept(
        state, (*chunk[:3], chunk[3] * 0.0, chunk[4])))


def _shared_expert_ungated(dep):
    """The timed path broken in the expert layer: the shared expert added
    whole, as every accepted model adds it."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm.moe
    _rewarmed(dep, hm, "moe", lambda p, *rest: kept(
        {k: v for k, v in p.items() if k != "shared_gate"}, *rest))


def _norms_times_w(dep):
    """The timed path broken in the norms: times w where this family
    multiplies by 1 + w."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm._rms
    _rewarmed(dep, hm, "_rms",
              lambda x, weight, eps, offset=0.0: kept(x, weight, eps))


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_no_decay, False, False, ("dlogit", "abs_dp", "choice_rel_diff")),
    (_shared_expert_ungated, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_norms_times_w, False, False, ("dlogit", "abs_dp", "choice_rel_diff")),
    (None, True, False, ("dlogit", "abs_dp", "choice_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path,
        monkeypatch):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment finds family, settings and
    reference by the configuration's names, preloads every ring through
    ``HistoryStore.restore``, counts the pairs served and the other chips'
    over the four expert layers, and the comparison follows the path
    under it. The control (matrices at fp8's 3 mantissa bits) comes out not
    correct on the compared numbers alone."""
    from benchmark.reference import gdn_moe_f32 as ref

    cell = _small_cell(str(tmp_path))
    held = {}
    blocks, forward = [], ref.forward
    monkeypatch.setattr(ref, "forward", lambda params, model, hist, filled: (
        blocks.append(len(hist)), forward(params, model, hist, filled))[1])

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 52, seconds=3.0,
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
    # every block of the reference has the same rows, the last one filled
    # up: 10 or 11 verdicts are 4 blocks of 3 (on the chip a block of 2
    # rows' programs never finished: PERF.md section 6, PR 52)
    assert blocks == [ref.ROW_BLOCK] * 4
    assert result["compared"]["rows_compared"][0] in (10, 11)
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
        assert dep.family_config.moe_layers == 4
        assert dep.registry.gauge("lm_gdn_log_decay_min").value() < -1
        assert dep.registry.counter("moe_pairs_absent_total").total() > 0
        grid = dep.scorer.executable_grid()
        assert grid["expert_body"] == "swiglu"
        assert grid["kinds"]["gdn"]["chunk"] == 32
        assert grid["kinds"]["gqa"]["qk_norm"] is True
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- the chip readings the limits were set from ------------------------------------------

with open(os.path.join(HERE, "qwen3next_limit_readings.json")) as _f:
    READINGS = json.load(_f)
SERVED_SEEDS = {run["seed"] for run in READINGS["served"]}


@pytest.mark.parametrize("kind", ["served", "control", "rolled",
                                  "proba_rolled"])
def test_the_chip_readings_the_limits_were_set_from_still_decide_alike(kind):
    """PR 52's runs of ``qwen3next_window_saturated`` on the chip: every
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
    "head_dim": 6, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 3, "linear_value_head_dim": 5,
    "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
    "num_experts_routed_over": 6, "moe_intermediate_size": 5,
    "shared_expert_intermediate_size": 7,
    "experts_held": {"first": 0, "count": 3}, "vocab_size": 50,
    "layers_kept": [0, 1, 2, 3, 4],
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 55,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one Gated DeltaNet mixer: keys 2 * 3 = 6, values 4 * 5 = 20; weights 8
    # * (12 + 40 + 8) + 20 * 8 = 640; a token 2 * 640 + 2 * 4 * 32 (taps on
    # q, k and v) + 4 value heads * 7 * 3 * 5 (the delta rule) = 1,956;
    # bytes 2 dispatches * 640 * 2 + 30 tokens * 8 * 8; four of the five
    # kept layers mix so
    ("gdn", 4 * 30 * 1956.0, 4 * (2 * 640 * 2 + 30 * 64.0)),
    # the attention layer: q with its gate 8 * 2 * 12, the output 12 * 8,
    # k and v 2 * 8 * 6: 384; a row's attention 2 heads * 55 pairs * 2 * 2
    # * 6 = 2,640
    ("gqa", 30 * 768.0 + 3 * 2640.0, 2 * 384 * 2 + 30 * 64.0),
    # experts: 55 pairs * 2 * 3 * 8 * 5 (three matrices); bytes: the 5
    # layers * (2 dispatches * 3 held * 120 values * 2 + 30 tokens * 64)
    ("experts", 55 * 240.0, 5 * (2 * 3 * 120 * 2 + 30 * 64.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # a layer's router 8 * 6, shared expert 3 * 8 * 7 and its gate 8: 224,
    # five of them; the untied head 2 * 8 * 50 a row, read once a dispatch
    rest_flop = 5 * 30 * 2 * 224.0 + 3 * 800.0
    rest_moved = (5 * 2 * 224 * 2 + 2 * 8 * 50 * 2 + 30 * (4 + 16.0)
                  + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)
    assert costs.layer_kinds(TOY) == ["gdn", "gdn", "gdn", "gqa", "gdn"]


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """A token: a Gated DeltaNet mixer 67.4 MFLOP of projections + 3.7 of
    the delta rule (and 0.07 of convolution): 71.1; an attention layer 54.5
    + 15.7 of causal scores and mix: 70.3; an expert layer the 2.5 held
    pairs 15.7, the shared expert, its gate and the router 8.4; by the
    matmuls 0.53 G multiply-adds a token (ISSUE 52), 17.5 TFLOP a dispatch
    of 8 windows at 12 layers. The count does not move with the chunk the
    program serves."""
    c = _real_config()
    tokens = 15360
    layers = len(c["layers_kept"])
    n_gqa = layers // 4
    n_gdn = layers - n_gqa
    work = {"dispatches": 1, "rows": 8, "tokens": tokens,
            "pairs": tokens * 2.5 * layers, "tokens_per_row": 1920}
    flop, moved = costs.part(c, work, "gdn")
    weights = 2048 * (12288 + 64) + 4096 * 2048  # 25.30 M + 8.39 M
    assert flop / (tokens * n_gdn) == 2.0 * weights + 2.0 * 4 * 8192 + (
        32 * 7.0 * 128 * 128)
    assert 71.0e6 < flop / (tokens * n_gdn) < 71.2e6
    assert moved == n_gdn * (weights * 2 + tokens * 2048 * 8.0)
    for chunk in (16, 128):
        assert costs.backbone(dict(c, gdn_chunk=chunk), work) == (
            costs.backbone(c, work))
    flop, _ = costs.part(c, work, "gqa")
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert 70.1e6 < flop / (tokens * n_gqa) < 70.4e6  # 54.5 + 15.7
    assert costs.part(c, work, "experts")[0] / (tokens * layers) == (
        2.5 * 6.0 * 2048 * 512)
    rest_flop, _ = costs.rest(c, work)
    outside = 2048 * 512 + 3 * 2048 * 512 + 2048
    assert (rest_flop - 8 * 2.0 * 2048 * 37984) / (tokens * layers) == (
        2.0 * outside)
    flop, moved = costs.backbone(c, work)
    matmuls = (n_gdn * weights + n_gqa * attention
               + layers * (outside + 2.5 * 3 * 2048 * 512)) / layers
    assert 0.52e9 < matmuls * 12 < 0.54e9  # multiply-adds a token, 12 layers
    if layers == 12:
        assert 17.4e12 < flop < 17.6e12
        assert 1140e6 < flop / tokens < 1141e6
    # the weights are read once a dispatch: 5,423 M parameters = 10.85 GB
    # at 12 layers, embedding and head among them
    held = (n_gdn * weights + n_gqa * attention
            + layers * (outside + 128 * 3 * 2048 * 512))
    if layers == 12:
        assert 5.422e9 < held + 2 * 2048 * 37984 < 5.424e9
    rows = 2 * layers * tokens * 2048 * 8.0  # sublayers in and out
    assert moved == held * 2 + 2048 * 37984 * 2 + rows + tokens * (
        4 + 2048 * 2) + 8 * 37984 * 4.0


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
    assert cap.programs == 2 and cap.busy_s == pytest.approx(840e-6)
    assert cap.seconds_under(["gdn"]) == pytest.approx(480e-6)
    assert cap.seconds_under(["gdn.project"]) == pytest.approx(160e-6)
    assert cap.seconds_under(["gdn.conv"]) == pytest.approx(50e-6)
    assert cap.seconds_under(["gdn.scan"]) == pytest.approx(240e-6)
    assert cap.seconds_under(["gdn.gate"]) == pytest.approx(30e-6)
    assert cap.seconds_under(["gqa"]) == pytest.approx(150e-6)
    assert cap.seconds_under(["moe.shared"]) == pytest.approx(30e-6)
    assert scopes.work(OBS) == {
        "dispatches": 2, "rows": 16.0, "tokens": 30720.0, "pairs": 921600.0,
        "tokens_per_row": 1920}


@pytest.mark.parametrize("metric,want", [
    ("gdn_device_share.sat", 100 * 480 / 840),
    ("gdn_scan_device_share.sat", 100 * (50 + 240 + 30) / 840)])
def test_a_device_share_is_the_scopes_share_of_busy_time(metric, want):
    assert _read(metric, OBS) == pytest.approx(want)


def test_the_mixers_roofline_share_is_cost_over_the_scopes_time(monkeypatch):
    """The recorded times are nobody's measurement, so the share comes out
    far over 100% and ``roofline_share`` refuses it: the test takes the
    refusal away and holds the arithmetic, and that the costs are the ones
    the configuration's ``costs.kind`` names (``gdn_moe``)."""
    import jax

    from benchmark.reduce import trace

    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    seen = {}

    def share(flop, moved, seconds, kind, n_devices=1, flop_peak=""):
        seen.update(flop=flop, moved=moved, seconds=seconds)
        return 50.0, "compute"

    monkeypatch.setattr(trace, "roofline_share", share)
    assert _read("gdn_roofline.sat", OBS) == 50.0
    want = costs.part(OBS["config"], scopes.work(OBS), "gdn")
    assert (seen["flop"], seen["moved"]) == want
    assert seen["seconds"] == pytest.approx(480e-6)


def test_the_mixers_floor_is_bound_by_compute():
    """Three projections of 33.7 M weights a token and a delta rule of 3.7
    MFLOP: operations over the chip's peak are many times the bytes over
    its bandwidth at 15,360 tokens a dispatch."""
    from benchmark.reduce import trace

    work = {"dispatches": 1, "rows": 8, "tokens": 15360, "pairs": 0,
            "tokens_per_row": 1920}
    flop, moved = costs.part(OBS["config"], work, "gdn")
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
    *((m, "scoped_cca_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_mla_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_mhc_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_ssm_dispatches.textproto") for m in OWN_METRICS),
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
                           "costs_gdn_moe.py")) as f:
        tree = ast.parse(f.read())
    imported = {a.name for node in ast.walk(tree)
                if isinstance(node, ast.Import) for a in node.names} | {
        node.module for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)}
    assert imported == {"__future__"}
    assert np.isfinite(costs.backbone(TOY, WORK)[0])
