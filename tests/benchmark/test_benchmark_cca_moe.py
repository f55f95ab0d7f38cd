"""The ``zaya1_window_saturated`` cell's files: the manifest resolves it
with its configuration, deployment, reference and every metric file; a
whole run of its deployment at the small preset on the CPU comes out
``correct`` until the timed path is broken; the cost functions give hand
counts; and the scope-based readers give the numbers worked out by hand
from ``benchmark/reduce/fixtures/scoped_cca_dispatches.textproto`` (a scope
named ``cca``), and nothing where a capture has no such scope."""

import ast
import gc
import json
import os
import shutil

import numpy as np
import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reduce import costs_cca_moe as costs
from benchmark.reduce import scopes

ROOT = benchmark_manifests.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
FIXTURE = os.path.join(FIXTURES, "scoped_cca_dispatches.textproto")
CELL = "zaya1_window_saturated"
# PR 30's six, two of the rooflines under the names the models share
NEW_METRICS = ("backbone_roofline.sat", "cca_roofline.sat",
               "expert_roofline.sat", "cca_device_share.sat",
               "router_device_share.sat", "skip_share.sat")
OWN_METRICS = ("cca_roofline.sat", "cca_device_share.sat",
               "router_device_share.sat", "skip_share.sat")


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kafka_history_zaya1.json")) as f:
        return json.load(f)


# -- the manifest ------------------------------------------------------------------

@benchmark_manifests.manifest_level
def test_the_manifest_resolves_the_cell_with_every_file_it_names():
    cell = benchmark_manifests.repo_manifest().resolve(CELL)
    assert cell.chips == 1 and cell.deployment_kind == "kafka_history_lm2"
    assert cell.generator_kind == "bus"
    assert cell.config_name == "kafka_history_zaya1"
    assert cell.traffic_name == "keyed_window_saturated"
    assert {m.name for m in cell.end_to_end} == {"tx_s", "setup_s"}
    reported = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= reported
    assert {"moe_device_share.sat", "pairs_per_token.sat",
            "expert_load_max_over_mean.sat", "device_idle.sat",
            "idle_wait_pct.sat", "dispatch_ms.sat", "period_ms.sat",
            "fetch_ms.sat", "idle_fetch_pct.sat"} <= reported
    # what is another model's alone, or reads nothing here, stays away
    assert not reported & {"kda_roofline.sat", "mla_roofline.sat",
                           "mla_device_share.sat",
                           "absent_pairs_per_token.sat",
                           "kernel_roofline.sat", "gather_offcpu_pct.sat"}
    for m in cell.per_layer:  # every reader a metric's file names is there
        manifest.load_kind("readers", cell.metric_docs[m.name]["reader"])
    manifest.load_kind("deployments", cell.deployment_kind)
    ref = manifest.load_kind("reference", cell.config["reference"]["module"])
    for name in ("make_params", "preload_rows", "sampled", "aux_path",
                 "served_and_expected", "compare"):
        assert callable(getattr(ref, name))
    assert set(cell.config["reference"]["limits"]) == {
        "mean_abs_dlogit", "max_abs_dp", "max_abs_dlogit_slice",
        "choice_rel_diff"}


@benchmark_manifests.manifest_level
def test_the_new_metrics_are_reported_in_the_new_cell_alone():
    """What is this model's alone; the two rooflines it shares by name are
    held to each model's own costs in ``test_benchmark_growth.py``."""
    ling = {m.name for m in benchmark_manifests.repo_manifest().resolve(
        "ling3_window_saturated").per_layer}
    assert not ling & set(OWN_METRICS)


@benchmark_manifests.manifest_level
def test_the_configuration_holds_every_published_width():
    """Every number of the catalog's ``config`` is in the file under the
    same key, but the depth, which ``reduced`` lists."""
    c = _real_config()
    published = {
        "hidden_size": 2048, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 2048, "num_experts": 16,
        "num_experts_per_tok": 1, "router_hidden_size": 256,
        "vocab_size": 262272, "cca_time0": 2, "cca_time1": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "tie_word_embeddings": True, "hidden_act": "silu",
        "attention_bias": False, "lm_head_bias": False,
        "sliding_window": None}
    assert {k: c[k] for k in published} == published
    assert c["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert c["layer_types"] == ["hybrid"] * 40
    assert c["num_hidden_layers"] == 20 == len(c["layers_kept"])
    assert c["published"] == {"num_hidden_layers": 40}
    assert set(c["reduced"]) == {"num_hidden_layers", "table_rows"}
    assert c["experts_held"] == {"first": 0, "count": 16}
    assert c["num_experts_routed_over"] == 17
    assert "two pipeline stages of 20 layers" in c["deployment_shape"]
    for key in ("residual_scaling", "cca_value_shift", "cca_convolutions",
                "cca_qk_mean", "cca_norms", "cca_rotary", "router", "skip",
                "weights", "left_out"):
        assert c["assumed"][key]
    entry = [e for e in benchmark_manifests.repo_doc()["configs"]
             if e["name"] == "kafka_history_zaya1"][0]
    assert entry["reduced"] == ["num_hidden_layers", "table_rows"]
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "cca_moe_f32.py")) as f:
        source = f.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "dataclasses", "functools", "math",
                        "time", "numpy", "jax", "benchmark"}, imported
    assert 'default_matmul_precision("highest")' in source


# -- whole runs at the small preset --------------------------------------------------

@pytest.fixture()
def service_gc():
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


def _small_cell(tmp_root: str):
    """The deployment's cell from ``zaya1_small_manifest.json``, its
    traffic cut to what three seconds on a CPU shared with the suite's
    other workers can carry (the rate needs two verdict batches)."""
    shutil.copy(os.path.join(HERE, "zaya1_small_manifest.json"),
                os.path.join(tmp_root, "BENCHMARK.json"))
    for name in ("benchmark", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tmp_root, name))
    cell = manifest.Manifest(tmp_root).resolve("zaya1_window_small")
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
    """The timed path broken in the expert layer: one expert's
    down-projection is zeros in every layer."""
    def change(layers):
        ffn = dict(layers["ffn"])
        experts = dict(ffn["experts"])
        experts["down"] = experts["down"].at[:, 0].set(0)
        layers["ffn"] = dict(ffn, experts=experts)

    _with_layers(dep, change)


def _cut_the_carry(dep):
    """The timed path broken in the router: no layer adds the state the
    layer before handed over (gamma is zeros)."""
    def change(layers):
        ffn = dict(layers["ffn"])
        router = dict(ffn["router"])
        router["gamma"] = router["gamma"] * 0.0
        layers["ffn"] = dict(ffn, router=router)

    _with_layers(dep, change)


def _drop_the_value_shift(dep):
    """The timed path broken in CCA: every value head reads the current
    token. The program's shift helper is replaced, its compiled programs
    dropped and warmed again (nothing may compile in the window)."""
    from ccfd_tpu.models import hybrid_moe as hm

    dep.undo = (hm, hm._back)
    kept = hm._back
    # the convolutions shift (B, T, 10, D) and keep their shift; the value
    # heads (B, T, 1, D) lose theirs
    hm._back = lambda x, keep: x * keep if x.shape[2] == 1 else kept(x, keep)
    hm.apply_serving.clear_cache()
    dep.scorer.warmup()


@pytest.mark.parametrize("sabotage,control,want,failing", [
    (None, False, True, ()),
    (_zero_an_expert, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (_cut_the_carry, False, False, ("dlogit", "abs_dp", "choice_rel_diff")),
    (_drop_the_value_shift, False, False,
     ("dlogit", "abs_dp", "choice_rel_diff")),
    (None, True, False, ("dlogit", "abs_dp", "choice_rel_diff")),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, sabotage, control, want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at the small preset: the deployment finds family, settings and
    reference by the configuration's names, preloads every ring through
    ``HistoryStore.restore``, and the comparison follows the path under it.
    The control (matrices at fp8's 3 mantissa bits) comes out not correct
    on the compared numbers alone."""
    cell = _small_cell(str(tmp_path))
    held = {}

    def wrapped(dep):
        held["dep"] = dep
        if sabotage is not None:
            sabotage(dep)

    try:
        result = core.run_cell(cell, seed=2**31 + 29, seconds=3.0,
                               trace=False, t_start=0.0, root=ROOT,
                               sabotage=wrapped, control=control)
    finally:
        undo = getattr(held.get("dep"), "undo", None)
        if undo is not None:
            setattr(undo[0], "_back", undo[1])
            undo[0].apply_serving.clear_cache()
    printed = capsys.readouterr().out
    assert result["correct"] is want, printed
    assert set(result["metrics"]) == {"tx_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "CHECK pairs_routed_minus_served: 0.0 == 0 -> ok" in printed
    assert "CHECK served_plus_skipped_minus_routed: 0.0 == 0 -> ok" in printed
    assert "CHECK customers_in_store_minus_preloaded: 0 == 0 -> ok" in printed
    assert "CHECK served_model: 'hybrid_moe' == 'hybrid_moe' -> ok" in printed
    # the four numbers decide as before; the shared comparison's new ones
    # and the miss controls are printed and decide nothing
    assert {"max_abs_dp", "max_abs_dlogit_slice"} <= set(result["compared"])
    assert not {"max_abs_dp_own", "max_row_rms_dlogit_slice"} & set(
        result["compared"])
    for line in ("INFO compared max_abs_dp_own: ",
                 "INFO compared max_row_rms_dlogit_slice: ",
                 "INFO miss_control rolled: mean_abs_dlogit ",
                 "INFO miss_control proba_rolled: mean_abs_dlogit "):
        assert line in printed
    failed = [line for line in printed.splitlines() if line.endswith("FAIL")]
    if want:
        assert not failed
    else:  # every other number held
        assert failed and all(any(word in line for word in failing)
                              for line in failed), failed


# -- the comparison, which kafka_history_mistral4's reference shares --------------------------

@pytest.mark.parametrize("control,moved,still", [
    ("rolled", ("max_row_rms_dlogit_slice", "mean_row_rms_dlogit_slice",
                "max_abs_dlogit_slice", "mean_abs_dlogit", "max_abs_dp",
                "choice_rel_diff"), ("max_abs_dp_own",)),
    ("proba_rolled", ("max_abs_dp_own", "max_abs_dp"),
     ("max_row_rms_dlogit_slice", "mean_row_rms_dlogit_slice",
      "max_abs_dlogit_slice", "mean_abs_dlogit", "choice_rel_diff"))])
def test_compare_gives_the_numbers_that_cannot_swing_beside_the_four(
        control, moved, still):
    """On made-up rows (8 of a vocabulary of 640; no model runs): a served
    set against its own expectation reads rounding in every number, and
    each miss control moves what it is there to move and nothing else."""
    from benchmark.reference import cca_moe_f32 as ref
    from benchmark.reference import mla_moe_f32
    from benchmark.reference.mlp_f32 import sigmoid

    assert mla_moe_f32.compare is ref.compare
    assert mla_moe_f32.miss_controls is ref.miss_controls
    with open(os.path.join(HERE, "zaya1_small_config.json")) as f:
        model = json.load(f)
    rng = np.random.default_rng(35)
    expect = {"logits": rng.standard_normal((8, 640)),
              "choice": rng.integers(0, 40, (8, 2, 5))}
    kept = expect["logits"].astype(np.float32)
    served = ref.Served(
        logits=kept, choice=expect["choice"].copy(), model=model,
        proba=sigmoid(ref.verdict_logit(kept, model)).astype(np.float32))
    own = ref.compare(served, expect)
    assert set(own) == set(moved) | set(still)
    assert all(value < 1e-6 for value in own.values()), own
    numbers = ref.compare(*ref.miss_controls(served, expect)[control])
    assert all(numbers[name] > 1e-3 for name in moved), numbers
    assert all(numbers[name] == own[name] for name in still), numbers
    if control == "rolled":  # a row's root mean square, its largest, their mean
        d = kept.astype(np.float64) - np.roll(expect["logits"], -1, axis=0)
        rms = np.sqrt((d * d).mean(-1))
        assert numbers["max_row_rms_dlogit_slice"] == pytest.approx(rms.max())
        assert numbers["mean_row_rms_dlogit_slice"] == pytest.approx(
            rms.mean())
    else:
        assert numbers["max_abs_dp_own"] == pytest.approx(np.abs(
            served.proba - np.roll(served.proba, -1)).max(), rel=1e-4)


# -- costs: hand counts at a small shape ------------------------------------------------

TOY = {
    "hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 2, "cca_time0": 2, "cca_time1": 2, "router_hidden_size": 3,
    "num_experts_routed_over": 5, "moe_intermediate_size": 6,
    "experts_held": {"first": 0, "count": 4}, "vocab_size": 50,
    "layers_kept": [0, 1],
    "costs": {"weight_bytes_per_value": 2, "in_bytes_per_value": 4},
}
WORK = {"dispatches": 2, "rows": 3, "tokens": 30, "pairs": 55,
        "tokens_per_row": 10}


@pytest.mark.parametrize("part,flop,moved", [
    # one CCA mixer: weights 8 * (4 + 2 * 2) * 2 + 4 * 2 * 8 + 2 taps * 6
    # heads * 2 * 2 = 128 + 64 + 48 = 240; a token: 2 * 240 + 2 * 2 taps *
    # 12 channels = 528; a row's attention 4 heads * 55 pairs * 2 * 2 * 2 =
    # 1760; bytes 2 dispatches * 240 * 2 + 30 * 8 * 8; two such layers
    ("cca", 2 * (30 * 528.0 + 3 * 1760.0), 2 * (2 * 240 * 2 + 30 * 64.0)),
    # experts: 55 pairs * 2 * 3 * 8 * 6; bytes: 2 layers * (2 dispatches *
    # 4 held * 144 values * 2 + 30 tokens * 64)
    ("experts", 55 * 288.0, 2 * (2 * 4 * 144 * 2 + 30 * 64.0)),
])
def test_costs_against_hand_counts(part, flop, moved):
    assert costs.part(TOY, WORK, part) == (flop, moved)


def test_the_backbone_is_its_parts_and_the_rest():
    # router 8 * 3 + 2 * 9 + 3 * 5 = 57 a layer; head 2 * 8 * 50 a row
    rest_flop = 2 * 30 * 2 * 57.0 + 3 * 800.0
    rest_moved = (2 * 2 * 57 * 2 + 2 * 8 * 50 * 2 + 30 * (4 + 16.0)
                  + 3 * 50 * 4.0)
    assert costs.rest(TOY, WORK) == (rest_flop, rest_moved)
    whole = costs.backbone(TOY, WORK)
    parts = [costs.part(TOY, WORK, p) for p in costs.PARTS]
    assert whole == (sum(p[0] for p in parts) + rest_flop,
                     sum(p[1] for p in parts) + rest_moved)


def test_a_token_of_the_real_configuration_costs_what_the_issue_reckoned():
    """A token and layer: CCA 11.1 MFLOP + 3.9 of attention, the router
    1.3, the one expert 25.2 where no skip is chosen; 9.4 GB of weights."""
    c = _real_config()
    work = {"dispatches": 1, "rows": 8, "tokens": 15360,
            "pairs": 15360 * 20, "tokens_per_row": 1920}
    per = 15360 * 20
    cca_flop, _ = costs.part(c, work, "cca")
    assert 14.9e6 < cca_flop / per < 15.2e6
    assert costs.part(c, work, "experts")[0] / per == 6.0 * 2048 * 2048
    rest_flop, _ = costs.rest(c, work)
    assert 1.3e6 < (rest_flop - 8 * 2.0 * 2048 * 262272) / per < 1.4e6
    flop, moved = costs.backbone(c, work)
    assert 12.3e12 < flop < 13.2e12
    assert 9.37e9 < moved - 40 * 15360 * 2048 * 8.0 < 9.6e9


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
    assert cap.seconds_under(["cca"]) == pytest.approx(300e-6)
    assert cap.seconds_under(["cca.attend"]) == pytest.approx(180e-6)
    assert cap.seconds_under(["moe.experts"]) == pytest.approx(240e-6)
    assert cap.seconds_under(["moe."]) == pytest.approx(280e-6)
    assert scopes.work(OBS) == {
        "dispatches": 2, "rows": 16.0, "tokens": 30720.0,
        "pairs": 590000.0, "tokens_per_row": 1920}


@pytest.mark.parametrize("metric,want", [
    ("cca_device_share.sat", 100 * 300 / 660),
    ("router_device_share.sat", 100 * 40 / 660),
    ("moe_device_share.sat", 100 * 280 / 660)])
def test_a_device_share_is_the_scopes_share_of_busy_time(metric, want):
    assert _read(metric, OBS) == pytest.approx(want)


@pytest.mark.parametrize("metric,part,scope_us", [
    ("cca_roofline.sat", "cca", 300),
    ("expert_roofline.sat", "experts", 240),
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


SCOPE_METRICS = ("cca_roofline.sat", "cca_device_share.sat",
                 "router_device_share.sat", "backbone_roofline.sat",
                 "expert_roofline.sat")


@pytest.mark.parametrize("metric,capture", [
    # an older commit: no scope on any operation, no counts in seq.wait
    *((m, "worker_and_loop.textproto") for m in SCOPE_METRICS),
    *((m, "/nonexistent") for m in SCOPE_METRICS),
    # the family's other model: programs, counts and scopes, none named cca
    ("cca_roofline.sat", "scoped_dispatches.textproto"),
    ("cca_device_share.sat", "scoped_dispatches.textproto")])
def test_a_capture_without_the_scope_gives_nothing(metric, capture):
    """The parent under this benchmark: the reader returns None and does
    not raise."""
    path = capture if capture.startswith("/") else os.path.join(
        FIXTURES, capture)
    assert _read(metric, dict(OBS, capture=path)) is None


def test_skip_share_reads_the_deployments_counters():
    before = {"moe_skipped_tokens_total": 10.0,
              "moe_routed_token_layers": 600.0,
              "moe_pairs_served_total": 590.0}
    after = {"moe_skipped_tokens_total": 130.0,
             "moe_routed_token_layers": 3000.0,
             "moe_pairs_served_total": 2870.0}
    obs = {"before": before, "after": after}
    assert _read("skip_share.sat", obs) == pytest.approx(5.0)
    # with every expert held the two shares are one whole
    assert _read("skip_share.sat", obs) + 100 * _read(
        "pairs_per_token.sat", obs) == pytest.approx(100.0)
    assert _read("skip_share.sat", {"before": {}, "after": {}}) is None
