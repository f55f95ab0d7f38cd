"""The ``xing4_window_saturated`` cell's files: the manifest resolves it
with its configuration, deployment, reference and every metric file; the
configuration keeps every number of the catalog's row but the depth; a
whole run of its deployment at the small preset on the CPU comes out
``correct`` until the timed path is broken (in the residual rule's maps, in
its Sinkhorn, in the streams' sum); the cost functions give hand counts and
what ISSUE 41 reckoned; and the scope-based readers give the numbers worked
out from ``benchmark/reduce/fixtures/scoped_mhc_dispatches.textproto``
(scopes ``hc`` > ``hc.maps`` / ``hc.mix``), and nothing where a capture has
no such scope."""

import ast
import gc
import json
import os
import shutil

import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reduce import costs_mhc_moe as costs
from benchmark.reduce import host_spans, scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
FIXTURE = os.path.join(FIXTURES, "scoped_mhc_dispatches.textproto")
CELL = "xing4_window_saturated"
OWN_METRICS = ("hc_roofline.sat", "hc_device_share.sat")
SHARED_METRICS = ("backbone_roofline.sat", "expert_roofline.sat",
                  "mla_roofline.sat", "mla_device_share.sat",
                  "moe_device_share.sat", "pairs_per_token.sat",
                  "expert_load_max_over_mean.sat", "fetch_ms.sat",
                  "idle_fetch_pct.sat")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DECIDING = ("mean_abs_dlogit", "choice_rel_diff", "max_abs_dp_own",
            "mean_row_rms_dlogit_slice")
PRINTED = ("max_abs_dp", "max_abs_dlogit_slice", "max_row_rms_dlogit_slice")


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kafka_history_xing4.json")) as f:
        return json.load(f)


# -- the manifest ------------------------------------------------------------------

@benchmark_manifests.manifest_level
def test_the_manifest_resolves_the_cell_with_every_file_it_names():
    cell = benchmark_manifests.repo_manifest().resolve(CELL)
    assert cell.chips == 1 and cell.deployment_kind == "kafka_history_lm3"
    assert cell.generator_kind == "bus"
    assert cell.config_name == "kafka_history_xing4"
    assert cell.traffic_name == "keyed_window_saturated"
    assert {m.name for m in cell.end_to_end} == {"tx_s", "setup_s"}
    reported = {m.name for m in cell.per_layer}
    assert set(OWN_METRICS) | set(SHARED_METRICS) <= reported
    assert {"device_idle.sat", "idle_wait_pct.sat", "dispatch_ms.sat",
            "router_service_us.sat", "idle_starved_pct.sat",
            "period_ms.sat", "worker_gap_ms.sat", "handoff_ms.sat",
            "loop_await_ms.sat", "loop_unowned_ms.sat"} <= reported
    # what is another model's alone, or reads nothing here, stays away:
    # no pair is absent where every expert is held, and the guarantee
    # holds that count to 0
    assert not reported & {
        "kda_roofline.sat", "kernel_roofline.sat", "cca_roofline.sat",
        "cca_device_share.sat", "skip_share.sat", "router_device_share.sat",
        "gather_offcpu_pct.sat", "absent_pairs_per_token.sat"}
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
        "kind"]).PARTS == ("mla", "experts", "hc")


@pytest.mark.parametrize("other", [
    "ling3_window_saturated", "zaya1_window_saturated",
    "mistral4_window_saturated", "history_saturated"])
@benchmark_manifests.manifest_level
def test_the_new_metrics_are_reported_in_the_new_cell_alone(other):
    theirs = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        other).per_layer}
    assert not theirs & set(OWN_METRICS)


@benchmark_manifests.manifest_level
def test_the_cell_and_its_entries_are_in_the_manifest():
    """Found by name, nothing counted: the configuration, the cell of one
    chip, its two metrics under the mixers' layer, and its name in the list
    of every metric it reports."""
    doc = benchmark_manifests.repo_doc()
    entry = {c["name"]: c for c in doc["configs"]}["kafka_history_xing4"]
    assert entry["reduced"] == ["num_hidden_layers", "table_rows"]
    assert entry["file"] == "benchmark/configs/kafka_history_xing4.json"
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "keyed_window_saturated"
    assert all(w["chips"] == 1 for w in doc["workloads"])
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    for name in OWN_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "Backbone mixers"
        assert per_layer[name]["moves"] == "tx_s"
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["unit"] == "%"
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"]
    assert CELL not in per_layer["absent_pairs_per_token.sat"]["workloads"]
    tx_s = {m["name"]: m for m in doc["end_to_end"]}["tx_s"]
    assert CELL in tx_s["workloads"]


@benchmark_manifests.manifest_level
def test_the_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's ``config`` is in the file with its
    value, but the depth, which ``reduced`` lists; no width, no expert and
    no vocabulary row is cut."""
    c = _real_config()
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    assert {k: c[k] for k in published} == published
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert "rope_parameters" not in c and "rope_interleave" not in c
    kept = c["layers_kept"]
    assert kept in ([1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 4, 5, 6])
    assert c["num_hidden_layers"] == len(kept)
    assert c["published"] == {"num_hidden_layers": 40}
    assert set(c["reduced"]) == {"num_hidden_layers", "table_rows"}
    assert c["experts_held"] == {"first": 0, "count": 64}
    assert c["num_experts_routed_over"] == 64
    assert c["layer_stack"] in ("scanned", "listed")
    assert "ep_size 1" in c["deployment_shape"]
    assert "pipeline stages" in c["deployment_shape"]
    for key in ("streams", "sinkhorn", "flat_norm", "mla_rotary",
                "softmax_scale", "router_bias", "shared_expert", "weights",
                "tokens", "readout", "length", "max_customers",
                "layer_stack", "left_out", "precision"):
        assert c["assumed"][key], key
    assert "next-token" in c["assumed"]["left_out"]
    assert c["serving"] == {"length": 64, "batch_sizes": [4, 8],
                            "compute_dtype": "bfloat16",
                            "max_customers": 131072, "inflight": 2}
    assert c["router"]["max_batch"] == 8
    assert c["preload"] == {"customers": 100000, "records": 64}
    assert any("served + absent = 4 x routed tokens" in g
               for g in c["guarantees"])
    assert c["costs"]["kind"] == "mhc_moe"
    assert c["reference"]["module"] == "mhc_moe_f32"
    entry = [e for e in benchmark_manifests.repo_doc()["configs"]
             if e["name"] == "kafka_history_xing4"][0]
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B"][0]
        assert c["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert key == "num_hidden_layers" or c[key] == value, key


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "mhc_moe_f32.py")) as f:
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
    for symbol in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                   "mhc_h_res_clamp_min", "rope_scaling", "rope_theta",
                   "first_k_dense_replace", "scoring_func", "topk_method",
                   "routed_scaling_factor", "num_nextn_predict_layers"):
        assert symbol in source  # the equations name their keys


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``xing4_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "xing4_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("xing4_window_small")
    cell.traffic["keys"] = dict(cell.traffic["keys"], customers=300)
    cell.traffic["warm_records"] = 16
    cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                    max_backlog=64, batch_records=16)
    return cell


def _with_maps(dep, change):
    """Every layer's ``res1`` and ``res2`` through ``change``."""
    params = dict(dep.scorer.params)
    params["layers"] = [dict(p, res1=change(p["res1"]),
                             res2=change(p["res2"]))
                        for p in params["layers"]]
    dep.scorer.params = params


def _drop_the_dynamic_terms(dep):
    """The timed path broken in the maps: alpha = 0, so the three maps are
    their biases' and the same for every token."""
    _with_maps(dep, lambda res: dict(res, alpha=res["alpha"] * 0.0))


def _mix_the_streams_evenly(dep):
    """The timed path broken in H_res: a bias that drowns the logits makes
    every stream-to-stream map the uniform one."""
    _with_maps(dep, lambda res: dict(
        res, alpha=res["alpha"].at[..., 2].set(0.0),
        b=res["b"].at[..., 8:].set(0.0)))


def _read_one_stream(dep):
    """The timed path broken at the read-out: the head reads four times the
    first stream in place of the streams' sum. The program's function is
    replaced, its compiled programs dropped and warmed again (nothing may
    compile in the window)."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm.slice_logits
    dep.undo = (hm, "slice_logits", kept)

    def first_stream(params, x, cfg, dtype=None):
        import jax.numpy as jnp

        return kept(params, jnp.broadcast_to(x[..., :1, :], x.shape), cfg,
                    dtype)

    hm.slice_logits = first_stream
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_drop_the_dynamic_terms, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_mix_the_streams_evenly, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_read_one_stream, False, False, ("dlogit", "abs_dp")),
    (None, True, False, ("dlogit", "abs_dp", "choice_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment finds family, settings and
    reference by the configuration's names, preloads every ring through
    ``HistoryStore.restore``, counts the pairs (none absent: every expert
    is held), and the comparison follows the path under it. The control
    (matrices at fp8's 3 mantissa bits, the maps' ``phi`` among them)
    comes out not correct on the compared numbers alone."""
    cell = _small_cell(str(tmp_path))
    held = {}

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 41, seconds=3.0,
                               trace=False, t_start=0.0, root=ROOT,
                               sabotage=wrapped, control=control)
    finally:
        undo = getattr(held.get("dep"), "undo", None)
        if undo is not None:
            setattr(*undo)
            undo[0].apply_serving.clear_cache()
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
        # the family's gauge: the deployment's registry holds the largest
        # defect of the run, no reference asked
        worst = held["dep"].registry.gauge("lm_hc_defect_max").value()
        assert 0 < worst < 0.2
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- the chip readings the limits were set from ------------------------------------------

with open(os.path.join(HERE, "xing4_limit_readings.json")) as _f:
    READINGS = json.load(_f)
SERVED_SEEDS = {run["seed"] for run in READINGS["served"]}


@pytest.mark.parametrize("kind", ["served", "control", "rolled",
                                  "proba_rolled"])
def test_the_chip_readings_the_limits_were_set_from_still_decide_alike(kind):
    """PR 41's runs of ``xing4_window_saturated`` on the chip: every served
    run passes every limit as the file sets it, every control run fails the
    three numbers that see precision, and each ``miss_control`` of a served
    run fails the number that is there for it. A later edit of a limit
    meets them."""
    limits = _real_config()["reference"]["limits"]
    runs = READINGS[kind]
    assert len(runs) >= (12 if kind != "control" else 4)
    for run in runs:
        over = {name for name in DECIDING if run[name] > limits[name]}
        if kind == "served":
            assert not over, run
        elif kind == "control":
            assert over == {"mean_abs_dlogit", "choice_rel_diff",
                            "mean_row_rms_dlogit_slice"}, run
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


def test_the_widest_gaps_swing_and_decide_nothing():
    """``max_abs_dp`` swings more than tenfold over the served seeds and
    overlaps the control; the mean over rows does not."""
    served, control = READINGS["served"], READINGS["control"]
    gaps = [run["max_abs_dp"] for run in served]
    assert max(gaps) > 10 * min(gaps)
    assert max(gaps) > min(run["max_abs_dp"] for run in control)
    mean = [run["mean_row_rms_dlogit_slice"] for run in served]
    assert max(mean) < 2 * min(mean)
    assert not set(PRINTED) & set(_real_config()["reference"]["limits"])


# -- costs: hand counts at a small shape ------------------------------------------------

TOY = {
    "hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 2,
    "qk_rope_head_dim": 2, "v_head_dim": 4, "q_lora_rank": 4,
    "kv_lora_rank": 3, "num_experts_routed_over": 8,
    "moe_intermediate_size": 6, "n_shared_experts": 1,
    "intermediate_size": 10, "first_k_dense_replace": 2, "hc_mult": 2,
    "experts_held": {"first": 0, "count": 8}, "vocab_size": 50,
    "layers_kept": [1, 2, 3],
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 55,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one MLA mixer, as costs_mla_moe counts it: weights 8 * 4 + 4 * 2 * 4 +
    # 8 * (3 + 2) + 3 * 2 * (2 + 4) + 2 * 4 * 8 = 204; a token 2 * 204; a
    # row's attention 2 heads * 55 pairs * 2 * (4 + 4) = 1760; bytes 2
    # dispatches * 204 * 2 + 30 tokens * 8 * 8; all three layers mix by MLA
    ("mla", 3 * (30 * 408.0 + 3 * 1760.0), 3 * (2 * 204 * 2 + 30 * 64.0)),
    # experts: 55 pairs * 2 * 3 * 8 * 6; bytes: the 2 expert layers (layer 1
    # is dense) * (2 dispatches * 8 held * 144 values * 2 + 30 tokens * 64)
    ("experts", 55 * 288.0, 2 * (2 * 8 * 144 * 2 + 30 * 64.0)),
    # hc, n = 2 streams: the maps have 2 * 2 + 4 = 8 outputs; a sublayer and
    # token: 2 * (16 * 8 + 16 + 6 * 8) = 384 operations; bytes: phi 16 * 8 *
    # 2 a dispatch + three passes of 2 * 8 float32 a token; 6 sublayers
    ("hc", 6 * 30 * 384.0, 6 * (2 * 256 + 30 * 192.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # an expert layer's router 8 * 8 and shared expert 3 * 8 * 6: 208, two
    # of them; the dense layer's feed-forward 3 * 8 * 10 = 240 (its tokens'
    # rows read and written: 30 * 64); head 2 * 8 * 50 a row
    rest_flop = 30 * 2 * (2 * 208.0 + 240.0) + 3 * 800.0
    rest_moved = (2 * (2 * 208 + 240) * 2 + 30 * 64.0 + 2 * 8 * 50 * 2
                  + 30 * (4 + 16.0) + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """A token and layer: MLA 56.8 MFLOP of projections + 19.7 of causal
    scores and mix at 192 + 128 wide, the shared expert 22.0, four routed
    pairs 88.1, the maps' product 1.4 (and 0.3 of mixing); the dense layer's
    feed-forward 198; 21.6 TFLOP a dispatch of 8 windows. The streams: 3
    passes x 14 sublayers x 15,360 tokens x 57,344 B = 37.0 GB, 45 ms at
    819 GB/s. (One dense + six expert layers; with five, a seventh
    less.)"""
    c = _real_config()
    layers = len(c["layers_kept"])
    sparse = layers - 1
    tokens = 15360
    work = {"dispatches": 1, "rows": 8, "tokens": tokens,
            "pairs": tokens * 4 * sparse, "tokens_per_row": 1920}
    mla_flop, mla_moved = costs.part(c, work, "mla")
    weights = 28409856  # 2.75 + 4.72 + 2.06 + 4.19 + 14.68 M
    assert (mla_moved - layers * tokens * 3584 * 8.0) / layers == 2 * weights
    per = mla_flop / (tokens * layers)
    assert 2 * weights == 56819712 and 76.4e6 < per < 76.6e6
    assert costs.part(c, work, "experts")[0] / (tokens * sparse) == (
        4 * 6.0 * 3584 * 1024)
    hc_flop, hc_moved = costs.part(c, work, "hc")
    assert hc_flop / (tokens * layers * 2) == 2.0 * (
        14336 * 24 + 14336 + 20 * 3584)
    assert hc_moved == layers * 2 * (14336 * 24 * 2 + tokens * 3 * 57344.0)
    if layers == 7:
        assert 36.9e9 < hc_moved < 37.1e9  # 45 ms at 819 GB/s
    rest_flop, _ = costs.rest(c, work)
    assert (rest_flop - 8 * 2.0 * 3584 * 131072) / tokens == 2.0 * (
        sparse * (3584 * 64 + 3 * 3584 * 1024) + 3 * 3584 * 9216)
    flop, moved = costs.backbone(c, work)
    if layers == 7:
        assert 21.5e12 < flop < 21.8e12
    # weights are read once a dispatch: 11.08 GB less the embedding, of
    # which a token reads a row
    held = layers * (weights + 2 * 14336 * 24) + sparse * (
        3584 * 64 + 65 * 3 * 3584 * 1024) + 3 * 3584 * 9216 + 3584 * 131072
    streams = layers * 2 * tokens * 3 * 57344.0
    rows = (layers + layers) * tokens * 3584 * 8.0  # sublayers in and out
    assert moved == held * 2 + streams + rows + tokens * (4 + 7168) \
        + 8 * 131072 * 4.0


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
    # 445 us a program: the row loop's 100 us are its body's, counted once
    assert cap.busy_s == pytest.approx(890e-6)
    assert cap.seconds_under(["hc"]) == pytest.approx(200e-6)
    assert cap.seconds_under(["hc.maps"]) == pytest.approx(60e-6)
    assert cap.seconds_under(["hc.mix"]) == pytest.approx(140e-6)
    # the rule's scope is around what it adds, never around the sublayers
    assert cap.seconds_under(["mla"]) == pytest.approx(300e-6)
    assert cap.seconds_under(["mla.attend"]) == pytest.approx(200e-6)
    assert cap.seconds_under(["moe."]) == pytest.approx(280e-6)
    assert cap.seconds_under(["dense_ffn"]) == pytest.approx(50e-6)
    assert scopes.work(OBS) == {
        "dispatches": 2, "rows": 16.0, "tokens": 30720.0,
        "pairs": 737280.0, "tokens_per_row": 1920}
    waits = host_spans.of(OBS).named("seq.wait")
    assert [e.stats["hc_defect"] for e in waits] == [0.03125, 0.0625]


@pytest.mark.parametrize("metric,want", [
    ("hc_device_share.sat", 100 * 200 / 890),
    ("mla_device_share.sat", 100 * 300 / 890),
    ("moe_device_share.sat", 100 * 280 / 890)])
def test_a_device_share_is_the_scopes_share_of_busy_time(metric, want):
    assert _read(metric, OBS) == pytest.approx(want)


@pytest.mark.parametrize("metric,part,scope_us", [
    ("hc_roofline.sat", "hc", 200),
    ("mla_roofline.sat", "mla", 300),
    ("expert_roofline.sat", "experts", 180),
    ("backbone_roofline.sat", "backbone", 890)])
def test_a_roofline_share_is_cost_over_the_scopes_time(
        monkeypatch, metric, part, scope_us):
    """The recorded times are nobody's measurement, so the share comes out
    far over 100% and ``roofline_share`` refuses it: the test takes the
    refusal away and holds the arithmetic, and that the costs are the ones
    the configuration's ``costs.kind`` names (``mhc_moe``)."""
    import jax

    from benchmark.reduce import trace

    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    seen = {}

    def share(flop, moved, seconds, kind, n_devices=1, flop_peak=""):
        seen.update(flop=flop, moved=moved, seconds=seconds)
        return 50.0, "bandwidth"

    monkeypatch.setattr(trace, "roofline_share", share)
    assert _read(metric, OBS) == 50.0
    work = scopes.work(OBS)
    want = (costs.backbone(OBS["config"], work) if part == "backbone"
            else costs.part(OBS["config"], work, part))
    assert (seen["flop"], seen["moved"]) == want
    assert seen["seconds"] == pytest.approx(scope_us * 1e-6)


def test_the_residual_rules_floor_is_bound_by_bandwidth():
    """At the published widths the rule multiplies almost nothing: its
    bytes over the chip's bandwidth are some fifty times its operations
    over the chip's peak."""
    from benchmark.reduce import trace

    flop, moved = costs.part(OBS["config"], scopes.work(OBS), "hc")
    share, bound = trace.roofline_share(
        flop, moved, 1.0, "TPU v5 lite", n_devices=1,
        flop_peak="bf16_flop_s")
    assert bound != "compute" and 0 < share < 100
    assert (moved / 819e9) > 40 * (flop / 197e12)


@pytest.mark.parametrize("metric,capture", [
    # an older commit: no scope on any operation, no counts in seq.wait
    *((m, "worker_and_loop.textproto") for m in OWN_METRICS),
    *((m, "/nonexistent") for m in OWN_METRICS),
    # the family's other models: programs, counts and scopes, none named hc
    *((m, "scoped_mla_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_cca_dispatches.textproto") for m in OWN_METRICS),
    *((m, "scoped_dispatches.textproto") for m in OWN_METRICS)])
def test_a_capture_without_the_scope_gives_nothing(metric, capture):
    """The parent under this benchmark, and the accepted cells' programs:
    the reader returns None and does not raise."""
    path = capture if capture.startswith("/") else os.path.join(
        FIXTURES, capture)
    assert _read(metric, dict(OBS, capture=path)) is None
