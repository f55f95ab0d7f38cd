"""The plain reference, the comparison that decides ``correct``, its
control (the int8 path, which has to come out not correct) and a whole run
with the timed path broken underneath."""

import ast
import gc
import json
import os

import numpy as np
import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.reference import mlp_f32, seq_f32, table

ROOT = benchmark_manifests.ROOT


def _config(name="seldon_rest_mlp"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rows():
    _, x, y = table.make_table(4096, 2**31 + 17)
    return x, y


@pytest.fixture(scope="module")
def reference(rows):
    params = mlp_f32.load_checkpoint(os.path.join(ROOT, "checkpoints"))
    return params, mlp_f32.forward(params, rows[0])


@pytest.mark.parametrize("module", ["mlp_f32.py", "seq_f32.py", "table.py"])
def test_the_reference_imports_nothing_of_the_program(module):
    with open(os.path.join(ROOT, "benchmark", "reference", module)) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    # "benchmark" is the reference's own package (the seeded table)
    assert imported <= {"__future__", "os", "numpy", "orbax",
                        "benchmark"}, imported


def test_the_table_is_its_wire_text_and_a_function_of_the_seed(rows):
    x, y = rows
    lines, again, _ = table.make_table(4096, 2**31 + 17)
    assert np.array_equal(x, again) and x.dtype == np.float32
    assert np.array_equal(table.parse_wire(lines), x)  # text round trip
    assert x.shape == (4096, 30) and int(y.sum()) == round(
        4096 * 492 / 284_807)
    other = table.make_table(4096, 2**31 + 18)[1]
    assert not np.array_equal(x, other)


def test_reference_agrees_with_the_programs_float32_forward(rows, reference):
    import jax

    from ccfd_tpu.cli import _restore_mlp_checkpoint
    from ccfd_tpu.models import mlp

    params, expect = reference
    theirs = jax.tree.map(np.asarray, _restore_mlp_checkpoint(
        os.path.join(ROOT, "checkpoints")))
    assert np.abs(mlp.apply_numpy(theirs, rows[0]) - expect).max() < 1e-6
    assert mlp_f32.flop_per_row(params) == 147_004
    # the rows span the model's range, so a swapped answer shows
    assert expect.min() < 1e-3 and expect.max() > 0.9


@pytest.mark.parametrize("family,want", [("flagship", True),
                                         ("control_mlp_q8", False)])
def test_limits_pass_the_flagship_and_fail_the_int8_control(
        rows, reference, family, want):
    """The configuration's limits, on the same rows: the served bf16 path
    is inside them, ``mlp_q8`` (int8 weights and activations, the step a
    later PR would be tempted by) is outside one of them."""
    from ccfd_tpu.serving.scorer import Scorer

    from benchmark.deployments.seldon_rest import (restore_params,
                                                   serving_section)

    config = _config()
    serving = serving_section(config, control=(family != "flagship"))
    scorer = Scorer(model_name=serving["model_name"],
                    params=restore_params(serving, ROOT),
                    compute_dtype=serving["compute_dtype"],
                    batch_sizes=(1024,), host_tier_rows=0)
    served = scorer.score(rows[0])
    numbers = mlp_f32.compare(served, reference[1])
    limits = config["reference"]["limits"]
    inside = all(numbers[k] <= limits[k] for k in limits)
    assert inside is want, (numbers, limits)


def test_compare_catches_an_altered_answer(reference):
    expect = reference[1]
    swapped = expect.copy()
    hi, lo = int(np.argmax(expect)), int(np.argmin(expect))
    swapped[[hi, lo]] = swapped[[lo, hi]]
    limits = _config()["reference"]["limits"]
    assert mlp_f32.compare(expect, expect)["max_abs_dp"] == 0.0
    assert mlp_f32.compare(swapped, expect)["max_abs_dp"] > limits[
        "max_abs_dp"]
    assert mlp_f32.compare(expect[:-1], expect)["max_abs_dp"] == np.inf
    nan = expect.copy()
    nan[0] = np.nan
    assert mlp_f32.compare(nan, expect)["mean_abs_dlogit"] == np.inf


@pytest.fixture()
def service_gc():
    """The deployment tunes the collector as the service does; put it back."""
    threshold = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*threshold)


@pytest.fixture()
def fused_kernel_here(monkeypatch):
    """The configuration's fused kernel on this backend too (interpret
    mode): the scorer picks it by itself only on a TPU."""
    from ccfd_tpu.serving import scorer

    init = scorer.Scorer.__init__

    def with_fused(self, *args, **kw):
        kw.setdefault("use_fused", True)
        init(self, *args, **kw)

    monkeypatch.setattr(scorer.Scorer, "__init__", with_fused)


def _break_score(dep):
    """The timed path broken where an answer is produced: every 97th
    probability of a batch comes back as its complement."""
    inner = dep._score

    def score(x):
        out = np.array(inner(x), np.float32)
        out[::97] = 1.0 - out[::97]
        return out

    dep._score = score


def _drop_starts(dep):
    """The timed path broken at the engine boundary: every 50th process
    start fails, which the router counts as a start error, so every
    guarantee of the configuration holds and the records are misses."""
    engine = dep.tap._engine
    inner = engine.start_process_batch

    def start_process_batch(def_id, variables_list, copy_vars=True):
        pids = list(inner(def_id, variables_list, copy_vars=copy_vars))
        pids[::50] = [None] * len(pids[::50])
        return pids

    engine.start_process_batch = start_process_batch


def _break_commit(dep):
    """The timed path broken where it keeps its state: the store takes a
    batch's records and publishes none, so every verdict is computed
    without the customer's history."""
    dep.scorer.store.commit = lambda token: True


def _break_history_score(dep):
    """Every 5th probability of a batch comes back as its complement (the
    history cell compares a sample of the verdicts, so the fault has to be
    dense enough for the sample to meet it)."""
    inner = dep.score_tap.score

    def score(txs, x):
        out = np.array(inner(txs, x), np.float32)
        out[::5] = 1.0 - out[::5]
        return out

    dep.score_tap.score = score


def _small(cell):
    """The cell at a size the CPU holds (the kernel in interpret mode)."""
    cell.config["table_rows"] = 2048
    if cell.deployment_kind == "kafka_history":
        cell.config["serving"] = dict(cell.config["serving"], length=32,
                                      batch_sizes=[64, 256])
        cell.config["router"] = dict(cell.config["router"], max_batch=256)
        cell.config["reference"] = dict(
            cell.config["reference"], sample_records=128,
            min_rows_compared=128)
        cell.traffic["keys"] = dict(cell.traffic["keys"], customers=300)
        cell.traffic["warm_records"] = 512
        cell.traffic["arrivals"] = dict(
            cell.traffic["arrivals"], max_backlog=1024, batch_records=256)
        return
    cell.config["serving"] = dict(cell.config["serving"],
                                  batch_sizes=[16, 1024])
    cell.config["router"] = dict(cell.config["router"], max_batch=1024)
    cell.traffic["warm_records"] = 1024
    arrivals = dict(cell.traffic["arrivals"])
    if arrivals["kind"] == "saturated":
        arrivals.update(max_backlog=4096, batch_records=1024)
    else:
        arrivals.update(rate_per_s=12000, warm_s=0.2)
    cell.traffic["arrivals"] = arrivals


@pytest.mark.parametrize("which,workload,sabotage,control,want,failing", [
    ("repo", "history_saturated", None, False, True, ()),
    ("repo", "history_saturated", _break_commit, False, False,
     ("dlogit", "abs_dp", "customers_in_store")),
    ("repo", "history_saturated", _break_history_score, False, False,
     ("dlogit", "abs_dp", "route")),
    ("repo", "history_saturated", None, True, False, ("dlogit", "abs_dp")),
    ("mlp", "pipeline_saturated", None, False, True, ()),
    ("mlp", "pipeline_saturated", _break_score, False, False,
     ("dlogit", "abs_dp", "route")),
    ("mlp", "pipeline_paced", None, False, True, ()),
    ("mlp", "pipeline_paced", _drop_starts, False, False,
     ("records_missed",)),
])
def test_a_whole_run_is_correct_until_the_timed_path_is_broken(
        service_gc, fused_kernel_here, which, workload, sabotage, control,
        want, failing, capsys, tmp_path):
    """Everything ``run.py`` does after it has found the chip, on the CPU
    at a small size: no timing is asserted, only that ``correct`` follows
    the path under it. ``control`` serves the configuration's int8 control,
    which has to come out not correct on the compared numbers alone
    (``mean_abs_dlogit`` always; the widest gap now and then too)."""
    cell = benchmark_manifests.load(which, str(tmp_path)).resolve(workload)
    _small(cell)
    result = core.run_cell(cell, seed=2**31 + 23, seconds=1.0, trace=False,
                           t_start=0.0, root=ROOT, sabotage=sabotage,
                           control=control)
    printed = capsys.readouterr().out
    assert result["correct"] is want, printed
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    json.dumps(result)  # the line can be printed
    # every number compared beside its limit, the line's verdict their sum
    assert all(core.check_line(name, *row) in printed
               for name, row in result["compared"].items())
    assert result["correct"] is all(
        ok for *_, ok in result["compared"].values())
    assert {"compiles_in_window", "rows_compared",
            "mean_abs_dlogit"} <= set(result["compared"])
    assert set(result["metrics"]) == {m.name for m in cell.end_to_end}
    assert result["attempted"] > 0
    assert (result["failed"] > 0) == (sabotage is _drop_starts)
    assert "CHECK mean_abs_dlogit" in printed  # each number beside its limit
    # these references hold no logits by row: no miss control, and no error
    assert "INFO miss_control" not in printed
    failed = [l for l in printed.splitlines() if l.endswith("FAIL")]
    if control:  # the served model is the control's: that check is its name
        failed = [l for l in failed if "served_model" not in l]
    if control:
        assert any("mean_abs_dlogit" in l for l in failed), failed
    if want:
        assert not failed
    else:  # every other number held
        assert failed and all(any(word in l for word in failing)
                              for l in failed), failed


@pytest.fixture(scope="module")
def history_case():
    """A seeded stream of 300 customers over a 2,048-row table, a sample of
    its records with the histories they must have had, the seeded weights
    and the reference's logits."""
    config = _config("kafka_history_seq")
    _, x, _ = table.make_table(2048, 2**31 + 29)
    params = seq_f32.make_params(config["model"])
    rng = np.random.default_rng(7)
    customer = rng.zipf(1.3, size=6000) % 300
    row_of = np.arange(6000) % 2048
    which = np.r_[rng.choice(6000, 95, replace=False), 5999]
    hist = seq_f32.histories(customer, row_of, x, which, 48)
    z = seq_f32.logits(params, hist, config["model"]["n_heads"])
    return config, params, customer, row_of, x, which, hist, z


def test_histories_are_a_customers_last_records_oldest_first(history_case):
    _, _, customer, row_of, x, which, hist, _ = history_case
    for j, i in enumerate(which):
        mine = [k for k in range(i + 1) if customer[k] == customer[i]][-48:]
        want = np.zeros((48, 30), np.float32)
        want[48 - len(mine):] = x[row_of[mine]]
        assert np.array_equal(hist[j], want)
    depth = (np.abs(hist).sum(-1) > 0).sum(-1)
    assert depth.min() < 48 and depth.max() == 48  # short and full ones


def _program_logits(params, hist, dtype, quantized=False):
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import logit

    from ccfd_tpu.models import seq
    from ccfd_tpu.ops import seq_quant

    tree = jax.tree.map(jnp.asarray, params)
    if quantized:
        p = seq_quant.apply_serving(seq_quant.quantize_seq(tree), hist,
                                    dtype, pos_length=hist.shape[1])
    else:
        p = seq.apply_serving(tree, hist, compute_dtype=dtype,
                              pos_length=hist.shape[1])
    return np.asarray(logit(p))


def test_seq_reference_agrees_with_the_programs_float32_forward(
        history_case):
    import jax.numpy as jnp

    _, params, *_, hist, z = history_case
    theirs = _program_logits(params, hist, jnp.float32)
    assert np.abs(theirs - z).max() < 2e-4
    # the seeded head keeps fraud rare without pinning every verdict
    assert z.std() > 0.2 and (z > 0).mean() < 0.05


@pytest.mark.parametrize("family,want", [("served_bf16", True),
                                         ("control_int8", False)])
def test_seq_limits_pass_bf16_and_fail_the_int8_control(
        history_case, family, want):
    """The configuration's limits on the same histories: the served bf16
    graph is inside them, the program's int8 path (``ops/seq_quant``) is
    outside one of them."""
    import jax.numpy as jnp

    config, params, *_, hist, z = history_case
    served = mlp_f32.sigmoid(_program_logits(
        params, hist, jnp.bfloat16, quantized=(family != "served_bf16")))
    numbers = seq_f32.compare(served, mlp_f32.sigmoid(z))
    limits = config["reference"]["limits"]
    inside = all(numbers[k] <= limits[k] for k in limits)
    assert inside is want, (numbers, limits)
