"""The ``mistral4_window_saturated`` cell's files: the manifest resolves it
with its configuration, deployment, reference and every metric file; the
configuration keeps every number of the catalog's row but the cut; a whole
run of its deployment at the small preset on the CPU comes out ``correct``
until the timed path is broken; the cost functions give hand counts; and
the scope-based readers give the numbers worked out by hand from
``benchmark/reduce/fixtures/scoped_mla_dispatches.textproto`` (scopes
``mla`` > ``mla.project`` / ``mla.attend``), and nothing where a capture
has no such scope."""

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
from benchmark.reduce import costs_mla_moe as costs
from benchmark.reduce import scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
FIXTURE = os.path.join(FIXTURES, "scoped_mla_dispatches.textproto")
CELL = "mistral4_window_saturated"
# PR 32's five, the three rooflines under the names the models share
NEW_METRICS = ("backbone_roofline.sat", "mla_roofline.sat",
               "expert_roofline.sat", "mla_device_share.sat",
               "absent_pairs_per_token.sat")
OWN_METRICS = ("mla_device_share.sat", "absent_pairs_per_token.sat")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the numbers that decide ``correct`` in kafka_history_mistral4
DECIDING = ("mean_abs_dlogit", "choice_rel_diff", "max_abs_dp_own",
            "mean_row_rms_dlogit_slice")
PRINTED = ("max_abs_dp", "max_abs_dlogit_slice", "max_row_rms_dlogit_slice")


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kafka_history_mistral4.json")) as f:
        return json.load(f)


# -- the manifest ------------------------------------------------------------------

@benchmark_manifests.manifest_level
def test_the_manifest_resolves_the_cell_with_every_file_it_names():
    cell = benchmark_manifests.repo_manifest().resolve(CELL)
    assert cell.chips == 1 and cell.deployment_kind == "kafka_history_lm3"
    assert cell.generator_kind == "bus"
    assert cell.config_name == "kafka_history_mistral4"
    assert cell.traffic_name == "keyed_window_saturated"
    assert {m.name for m in cell.end_to_end} == {"tx_s", "setup_s"}
    reported = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= reported
    assert {"moe_device_share.sat", "pairs_per_token.sat",
            "expert_load_max_over_mean.sat", "device_idle.sat",
            "idle_wait_pct.sat", "dispatch_ms.sat", "router_service_us.sat",
            "idle_starved_pct.sat", "period_ms.sat", "fetch_ms.sat",
            "idle_fetch_pct.sat"} <= reported
    # what is another model's alone, or reads nothing here, stays away
    assert not reported & {
        "kda_roofline.sat", "kernel_roofline.sat", "cca_roofline.sat",
        "cca_device_share.sat", "skip_share.sat", "router_device_share.sat",
        "gather_offcpu_pct.sat"}
    for m in cell.per_layer:  # every reader a metric's file names is there
        manifest.load_kind("readers", cell.metric_docs[m.name]["reader"])
    manifest.load_kind("deployments", cell.deployment_kind)
    ref = manifest.load_kind("reference", cell.config["reference"]["module"])
    for name in ("make_params", "preload_rows", "sampled", "aux_path",
                 "served_and_expected", "compare"):
        assert callable(getattr(ref, name))
    assert set(cell.config["reference"]["limits"]) == set(DECIDING) == set(
        cell.config["reference"]["limits_why"])
    # no widest gap decides: each swings with the seed (PR 35)
    assert not set(PRINTED) & set(cell.config["reference"]["limits"])


@pytest.mark.parametrize("other", ["ling3_window_saturated",
                                   "zaya1_window_saturated",
                                   "history_saturated"])
@benchmark_manifests.manifest_level
def test_the_new_metrics_are_reported_in_the_new_cell_alone(other):
    """What is this model's alone; the three rooflines it shares by name
    are held to each model's own costs in ``test_benchmark_growth.py``."""
    theirs = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        other).per_layer}
    assert not theirs & set(OWN_METRICS)


@benchmark_manifests.manifest_level
def test_the_benchmark_has_four_configurations_and_five_cells_of_one_chip():
    """They are there, whatever else is: found by name, nothing counted."""
    doc = benchmark_manifests.repo_doc()
    assert {"kafka_history_seq", "kafka_history_ling3", "kafka_history_zaya1",
            "kafka_history_mistral4"} <= {c["name"] for c in doc["configs"]}
    cells = {w["name"]: w for w in doc["workloads"]}
    for name in ("history_saturated", "history_sparse_saturated",
                 "ling3_window_saturated", "zaya1_window_saturated", CELL):
        assert cells[name]["chips"] == 1
        assert len(cells[name]["why"]) <= 200
    assert "attention sees 4x its share of tokens" in cells[CELL]["why"]
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "tx_s"


@benchmark_manifests.manifest_level
def test_the_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog's ``config`` is in the file with its
    value, but the three that ``reduced`` lists."""
    c = _real_config()
    published = {
        "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 12288, "kv_lora_rank": 256,
        "max_position_embeddings": 1048576, "mlp_bias": False,
        "model_type": "mistral4", "moe_intermediate_size": 2048,
        "n_group": 1, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "routed_scaling_factor": 1, "sliding_window": None,
        "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128}
    assert {k: c[k] for k in published} == published
    assert c["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    cut = {"num_hidden_layers": (6, 36), "n_routed_experts": (32, 128),
           "vocab_size": (32768, 131072)}
    for key, (here, theirs) in cut.items():
        assert c[key] == here and c["published"][key] == theirs
    assert set(c["reduced"]) == set(cut) | {"table_rows"}
    assert c["layers_kept"] == [0, 1, 2, 3, 4, 5]
    assert c["experts_held"] == {"first": 0, "count": 32}
    assert c["num_experts_routed_over"] == 128
    assert c["layer_stack"] in ("scanned", "listed")
    assert "4 chips share each layer" in c["deployment_shape"]
    assert "pipeline stages" in c["deployment_shape"]
    for key in ("softmax_scale", "query_scale", "scoring_func", "router_bias",
                "shared_expert", "mla_norms", "mla_rotary", "tokens",
                "readout", "weights", "length", "max_customers",
                "layer_stack", "left_out"):
        assert c["assumed"][key]
    assert c["serving"] == {"length": 64, "batch_sizes": [4, 8],
                            "compute_dtype": "bfloat16",
                            "max_customers": 131072, "inflight": 2}
    assert c["router"]["max_batch"] == 8
    assert c["preload"] == {"customers": 100000, "records": 64}
    assert any("served + absent = 4 x routed tokens" in g
               for g in c["guarantees"])
    entry = [e for e in benchmark_manifests.repo_doc()["configs"]
             if e["name"] == "kafka_history_mistral4"][0]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "table_rows"]
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Mistral-Small-4-119B-2603"][0]
        assert c["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert key in cut or c[key] == value, key


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "mla_moe_f32.py")) as f:
        source = f.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "math", "time", "numpy",
                        "jax", "benchmark"}, imported
    assert 'default_matmul_precision("highest")' in source
    for symbol in ("q_lora_rank", "rope_interleave", "beta_fast",
                   "llama_4_scaling_beta", "mscale_all_dim",
                   "num_experts_per_tok", "n_shared_experts"):
        assert symbol in source  # the equations name their keys


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``mistral4_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "mistral4_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("mistral4_window_small")
    cell.traffic["keys"] = dict(cell.traffic["keys"], customers=300)
    cell.traffic["warm_records"] = 16
    cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                    max_backlog=64, batch_records=16)
    return cell


def _with_layers(dep, change):
    params = dict(dep.scorer.params)
    layers = dict(params["layers"])
    change(layers)
    dep.scorer.params = dict(params, layers=layers)


def _zero_an_expert(dep):
    """The timed path broken in the expert layer: one held expert's
    down-projection is zeros in every layer."""
    def change(layers):
        ffn = dict(layers["ffn"])
        experts = dict(ffn["experts"])
        experts["down"] = experts["down"].at[:, 0].set(0)
        layers["ffn"] = dict(ffn, experts=experts)

    _with_layers(dep, change)


def _flatten_the_query_latent(dep):
    """The timed path broken in MLA's low-rank query path: the latent's
    norm weight is zeros, so every query is and attention is a mean."""
    def change(layers):
        mixer = dict(layers["mixer"])
        mixer["q_norm"] = mixer["q_norm"] * 0.0
        layers["mixer"] = mixer

    _with_layers(dep, change)


def _rotate_by_halves(dep):
    """The timed path broken in the rotary: the pairs are the two halves
    whatever the settings say. The program's helper is replaced, its
    compiled programs dropped and warmed again (nothing may compile in the
    window)."""
    from ccfd_tpu.models import hybrid_moe as hm

    kept = hm._rotary
    dep.undo = (hm, "_rotary", kept)
    hm._rotary = lambda x, position, freq, interleaved=False, scale=1.0: \
        kept(x, position, freq, False, scale)
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_zero_an_expert, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_flatten_the_query_latent, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_rotate_by_halves, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (None, True, False, ("dlogit", "abs_dp", "choice_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment finds family, settings and
    reference by the configuration's names, preloads every ring through
    ``HistoryStore.restore``, counts what the share leaves to the other
    chips, and the comparison follows the path under it. The control
    (matrices at fp8's 3 mantissa bits) comes out not correct on the
    compared numbers alone."""
    cell = _small_cell(str(tmp_path))
    held = {}

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 31, seconds=3.0,
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
    assert "served_plus_skipped_minus_routed" not in printed  # 8 of 32 held
    assert "CHECK customers_in_store_minus_preloaded: 0 == 0 -> ok" in printed
    assert "CHECK served_model: 'hybrid_moe' == 'hybrid_moe' -> ok" in printed
    # what decides nothing is printed beside what does
    for line in (*(f"INFO compared {name}: " for name in PRINTED),
                 "INFO miss_control rolled: mean_abs_dlogit ",
                 "INFO miss_control proba_rolled: mean_abs_dlogit "):
        assert line in printed
    assert {"rows_compared", *DECIDING} <= set(result["compared"])
    assert not set(PRINTED) & set(result["compared"])
    failed = [line for line in printed.splitlines() if line.endswith("FAIL")]
    if want:
        assert not failed
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- the comparison's numbers, each against the fault it is there for ---------------------

@pytest.fixture(scope="module")
def served_set():
    """``(ref, limits, served, expect)`` at the small preset: the
    reference's logits and routing of 12 seeded windows as the
    expectation, and as what was served the same logits moved in float32's
    last bits, their own verdicts, the same routing."""
    from benchmark.reference import mla_moe_f32 as ref
    from benchmark.reference import table
    from benchmark.reference.mlp_f32 import sigmoid

    with open(os.path.join(HERE, "mistral4_small_config.json")) as f:
        config = json.load(f)
    rng = np.random.default_rng(35)
    _, rows, _ = table.make_table(int(config["table_rows"]), 2**31 + 35)
    n, length = 12, int(config["serving"]["length"])
    logits, choice = ref.forward(
        ref.make_params(config), config,
        rows[rng.integers(0, len(rows), (n, length))],
        np.full(n, length, np.int32))
    expect = {"logits": np.asarray(logits), "choice": choice}
    kept = (expect["logits"] * (1 + 1e-6 * rng.standard_normal(
        expect["logits"].shape))).astype(np.float32)
    served = ref.Served(
        logits=kept, choice=choice.copy(), model=config,
        proba=sigmoid(ref.verdict_logit(kept, config)).astype(np.float32))
    return ref, config["reference"]["limits"], served, expect


def _rolled(ref, served, expect):
    return ref.miss_controls(served, expect)["rolled"]


def _proba_rolled(ref, served, expect):
    return ref.miss_controls(served, expect)["proba_rolled"]


def _one_rows_logits_are_anothers(ref, served, expect):
    logits = served.logits.copy()
    logits[3] = logits[7]
    return dataclasses.replace(served, logits=logits), expect


def _a_head_over_another_slice(ref, served, expect):
    return served, dict(expect, logits=np.roll(expect["logits"], 1, axis=1))


def _one_outlying_logit(ref, served, expect):
    """What a limit on ``max_abs_dlogit_slice`` refuses and the accepted
    program produces: one logit of 6,144, no answer token's, off by 2."""
    logits = served.logits.copy()
    logits[5, 17] += 2.0
    assert 17 not in (served.model["readout"]["fraud_id"],
                      served.model["readout"]["legit_id"])
    return dataclasses.replace(served, logits=logits), expect


@pytest.mark.parametrize("fault,fails,holds", [
    (None, (), DECIDING),
    # a misordered answer: every number against the reference, not the
    # run's own
    (_rolled, ("mean_row_rms_dlogit_slice", "mean_abs_dlogit",
               "choice_rel_diff"), ("max_abs_dp_own",)),
    # an altered verdict over the right logits: its own number alone
    (_proba_rolled, ("max_abs_dp_own",),
     ("mean_abs_dlogit", "choice_rel_diff", "mean_row_rms_dlogit_slice")),
    # one row of 12: the verdict is no longer its logits'; the mean over
    # rows leaves it to that number, the largest row (printed) sees it
    (_one_rows_logits_are_anothers, ("max_abs_dp_own",),
     ("choice_rel_diff",)),
    (_a_head_over_another_slice, ("mean_row_rms_dlogit_slice",),
     ("max_abs_dp_own", "choice_rel_diff")),
    (_one_outlying_logit, (), DECIDING),
])
def test_each_deciding_number_fails_the_fault_it_is_there_for(
        served_set, fault, fails, holds):
    ref, limits, served, expect = served_set
    assert set(limits) == set(DECIDING)
    if fault is not None:
        served, expect = fault(ref, served, expect)
    numbers = ref.compare(served, expect)
    assert set(DECIDING) | set(PRINTED) <= set(numbers)
    for name in fails:  # with the room the real limits are held to
        assert numbers[name] > 3 * limits[name], (name, numbers[name])
    for name in holds:
        assert numbers[name] <= limits[name], (name, numbers[name])
    if fault is _one_outlying_logit:  # the widest gap does see it
        assert numbers["max_abs_dlogit_slice"] > 1.9
    if fault is _one_rows_logits_are_anothers:
        assert numbers["max_row_rms_dlogit_slice"] > 0.3


def test_the_miss_controls_leave_the_run_as_it_was(served_set):
    ref, _, served, expect = served_set
    before = ref.compare(served, expect)
    controls = ref.miss_controls(served, expect)
    assert list(controls) == ["rolled", "proba_rolled"]
    assert ref.compare(served, expect) == before
    s, e = controls["rolled"]
    assert s is served and np.array_equal(e["logits"][0], expect["logits"][1])
    assert np.array_equal(e["choice"][-1], expect["choice"][0])
    s, e = controls["proba_rolled"]
    assert e is expect and s.logits is served.logits
    assert np.array_equal(s.proba[:-1], served.proba[1:])


with open(os.path.join(HERE, "mistral4_limit_readings.json")) as _f:
    READINGS = json.load(_f)


@pytest.mark.parametrize("kind", ["served", "control", "rolled",
                                  "proba_rolled"])
def test_the_chip_readings_the_limits_were_set_from_still_decide_alike(kind):
    """PR 35's runs of ``mistral4_window_saturated`` on the chip: every
    served run passes every limit as the file sets it, every control run
    fails the two numbers that stay, and each ``miss_control`` fails the
    number that is there for it. A later edit of a limit meets them."""
    limits = _real_config()["reference"]["limits"]
    runs = READINGS[kind]
    assert len(runs) >= (40 if kind != "control" else 8)
    for run in runs:
        over = {name for name in DECIDING if run[name] > limits[name]}
        if kind == "served":
            assert not over, run
        elif kind == "control":
            assert {"mean_abs_dlogit", "choice_rel_diff"} <= over, run
        elif kind == "rolled":
            assert "mean_row_rms_dlogit_slice" in over, run
            assert "max_abs_dp_own" not in over, run
        else:
            assert over == {"max_abs_dp_own"}, run


@pytest.mark.parametrize("name,miss", [
    ("max_abs_dp_own", "proba_rolled"),
    ("mean_row_rms_dlogit_slice", "rolled")])
def test_a_new_limit_has_threefold_room_on_both_sides(name, miss):
    limit = _real_config()["reference"]["limits"][name]
    assert limit >= 3 * max(run[name] for run in READINGS["served"])
    assert limit <= min(run[name] for run in READINGS[miss]) / 3


def test_the_largest_row_has_no_ninefold_room_and_so_decides_nothing():
    """The readings that put the mean over rows in the largest row's
    place (ISSUE 35's fallback): the largest of 33 rows' errors swings
    with the seed as the widest gaps do."""
    name = "max_row_rms_dlogit_slice"
    served = [run[name] for run in READINGS["served"]]
    assert 9 * max(served) > min(run[name] for run in READINGS["rolled"])
    assert max(served) > 4 * min(served)
    mean = [run["mean_row_rms_dlogit_slice"] for run in READINGS["served"]]
    assert max(mean) < 2.5 * min(mean)


# -- costs: hand counts at a small shape ------------------------------------------------

TOY = {
    "hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 2,
    "qk_rope_head_dim": 2, "v_head_dim": 4, "q_lora_rank": 4,
    "kv_lora_rank": 3, "num_experts_routed_over": 8,
    "moe_intermediate_size": 6, "n_shared_experts": 1,
    "experts_held": {"first": 0, "count": 2}, "vocab_size": 50,
    "layers_kept": [0, 1],
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 55,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one MLA mixer: weights 8 * 4 + 4 * 2 * 4 + 8 * (3 + 2) + 3 * 2 * (2 +
    # 4) + 2 * 4 * 8 = 32 + 32 + 40 + 36 + 64 = 204; a token: 2 * 204; a
    # row's attention 2 heads * 55 pairs * 2 * (4 + 4) = 1760; bytes 2
    # dispatches * 204 * 2 + 30 tokens * 8 * 8; two such layers
    ("mla", 2 * (30 * 408.0 + 3 * 1760.0), 2 * (2 * 204 * 2 + 30 * 64.0)),
    # experts: 55 pairs * 2 * 3 * 8 * 6; bytes: 2 layers * (2 dispatches *
    # 2 held * 144 values * 2 + 30 tokens * 64)
    ("experts", 55 * 288.0, 2 * (2 * 2 * 144 * 2 + 30 * 64.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # a layer's router 8 * 8 and shared expert 3 * 8 * 6: 208; head 2 * 8 *
    # 50 a row
    rest_flop = 2 * 30 * 2 * 208.0 + 3 * 800.0
    rest_moved = (2 * 2 * 208 * 2 + 2 * 8 * 50 * 2 + 30 * (4 + 16.0)
                  + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """A token and layer: MLA 56.1 MFLOP of projections + 15.7 of causal
    scores and mix, the shared expert 50.3, one held pair 50.3, the router
    1.0; 16.0 TFLOP a dispatch of 8 windows; 10.85 GB of weights."""
    c = _real_config()
    per = 15360 * 6
    work = {"dispatches": 1, "rows": 8, "tokens": 15360, "pairs": per,
            "tokens_per_row": 1920}
    mla_flop, mla_moved = costs.part(c, work, "mla")
    assert (mla_moved - per * 4096 * 8.0) / 6 == 2 * 28049408
    assert 71.7e6 < mla_flop / per < 71.9e6
    assert costs.part(c, work, "experts")[0] / per == 6.0 * 4096 * 2048
    rest_flop, _ = costs.rest(c, work)
    assert (rest_flop - 8 * 2.0 * 4096 * 32768) / per == 2.0 * (
        4096 * 128 + 3 * 4096 * 2048)
    flop, moved = costs.backbone(c, work)
    assert 15.9e12 < flop < 16.1e12
    weights = 6 * (28049408 + 25165824 + 524288 + 32 * 25165824) + 2 * (
        32768 * 4096)
    assert weights * 2 == 10845421568
    # the embedding is read a row a token, not whole
    assert moved - 12 * 15360 * 4096 * 8.0 - 15360 * (4 + 8192) \
        - 8 * 32768 * 4.0 == (weights - 32768 * 4096) * 2


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
    # 330 us a program: the scan's 300 us are its body's, counted once
    assert cap.busy_s == pytest.approx(660e-6)
    assert cap.seconds_under(["mla"]) == pytest.approx(300e-6)
    assert cap.seconds_under(["mla.project"]) == pytest.approx(100e-6)
    assert cap.seconds_under(["mla.attend"]) == pytest.approx(200e-6)
    assert cap.seconds_under(["moe.experts"]) == pytest.approx(180e-6)
    assert cap.seconds_under(["moe.shared"]) == pytest.approx(80e-6)
    assert cap.seconds_under(["moe."]) == pytest.approx(280e-6)
    assert scopes.work(OBS) == {
        "dispatches": 2, "rows": 16.0, "tokens": 30720.0,
        "pairs": 184320.0, "tokens_per_row": 1920}


@pytest.mark.parametrize("metric,want", [
    ("mla_device_share.sat", 100 * 300 / 660),
    ("moe_device_share.sat", 100 * 280 / 660)])
def test_a_device_share_is_the_scopes_share_of_busy_time(metric, want):
    assert _read(metric, OBS) == pytest.approx(want)


@pytest.mark.parametrize("metric,part,scope_us", [
    ("mla_roofline.sat", "mla", 300),
    ("expert_roofline.sat", "experts", 180),
    ("backbone_roofline.sat", "backbone", 660)])
def test_a_roofline_share_is_cost_over_the_scopes_time(
        monkeypatch, metric, part, scope_us):
    """The recorded times are nobody's measurement (a dispatch takes
    hundreds of milliseconds, not 330 us), so the share comes out far over
    100% and ``roofline_share`` refuses it: the test takes the refusal
    away and holds the arithmetic, and that the costs are the ones the
    configuration's ``costs.kind`` names."""
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


SCOPE_METRICS = ("mla_roofline.sat", "mla_device_share.sat",
                 "backbone_roofline.sat", "expert_roofline.sat")


@pytest.mark.parametrize("metric,capture", [
    # an older commit: no scope on any operation, no counts in seq.wait
    *((m, "worker_and_loop.textproto") for m in SCOPE_METRICS),
    *((m, "/nonexistent") for m in SCOPE_METRICS),
    # the family's second model: programs, counts and scopes, none named mla
    ("mla_roofline.sat", "scoped_cca_dispatches.textproto"),
    ("mla_device_share.sat", "scoped_cca_dispatches.textproto")])
def test_a_capture_without_the_scope_gives_nothing(metric, capture):
    """The parent under this benchmark: the reader returns None and does
    not raise."""
    path = capture if capture.startswith("/") else os.path.join(
        FIXTURES, capture)
    assert _read(metric, dict(OBS, capture=path)) is None


def test_absent_pairs_read_the_deployments_counters():
    before = {"moe_pairs_absent_total": 30.0,
              "moe_routed_token_layers": 10.0,
              "moe_pairs_served_total": 10.0}
    after = {"moe_pairs_absent_total": 3000.0,
             "moe_routed_token_layers": 1000.0,
             "moe_pairs_served_total": 1000.0}
    obs = {"before": before, "after": after}
    assert _read("absent_pairs_per_token.sat", obs) == pytest.approx(3.0)
    # with the pairs served here it is the experts a token chooses
    assert _read("absent_pairs_per_token.sat", obs) + _read(
        "pairs_per_token.sat", obs) == pytest.approx(4.0)
    # the parent's program has no such counter: nothing, and no error
    assert _read("absent_pairs_per_token.sat",
                 {"before": {}, "after": {}}) is None
    assert _read("absent_pairs_per_token.sat", {
        "before": {"moe_routed_token_layers": 1.0},
        "after": {"moe_routed_token_layers": 9.0}}) is None
