"""bench.py's compact summary line (VERDICT r4 item 3): the driver keeps
only the last ~2000 chars of bench output, so the FINAL printed line must
be one complete, small JSON object carrying the contract keys — the full
record printed before it got truncated two rounds running (BENCH_r03/r04
both recorded "parsed": null)."""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = None


def _load_bench():
    global _BENCH
    if _BENCH is None:
        spec = importlib.util.spec_from_file_location(
            "ccfd_bench_summary", os.path.join(REPO, "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _BENCH = mod
    return _BENCH


def _full_result():
    """A worst-case full record: every section present, with the
    unbounded sub-trees (latency grids, client lists) stuffed far past
    the driver's window."""
    return {
        "metric": "end_to_end_scoring_throughput_mlp_bf16",
        "value": 317700.0, "unit": "tx/s", "vs_baseline": 6.354,
        "p50_ms": 1.1, "p99_ms": 2.2, "p99_e2e_ms": 2.7,
        "p99_vs_target": 3.7, "fused_active": True, "platform": "tpu",
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "latency_batch": {str(b): {"p50": 1, "p99": 2}
                          for b in (256, 1024, 4096, 16384, 65536)},
        "rest": {"tx_s": 347000.0, "requests_s": 84.0, "p50_ms": 1.9,
                 "p99_ms": 2.7, "transport": "native",
                 "rows_per_request": 4096, "host_tier_rows": 0,
                 "errors": 0, "clients": list(range(2000))},
        "pipeline": {"tx_s": 52000.0, "paced_rate_tx_s": 50000.0,
                     "p50_ms": 3.1, "p99_ms": 8.5,
                     "standard_starts": 12345, "fraud_starts": 77},
        "mesh": {"tx_s": 1.0e6, "devices": 8},
        "retrain": {"steps_s": 40.0, "labels_s": 41000.0, "batch": 1024,
                    "devices": 1, "final_loss": 0.08},
        "seq": {"histories_s": 293000.0, "batch": 4096, "seq_len": 32,
                "histories_s_single_device": 250000.0,
                "histories_s_ring": 293000.0},
        "zoo": {name: {"tx_s": 1000.0 * i, "batch": 16384}
                for i, name in enumerate(
                    ("logreg", "gbt", "gbt_mxu", "gbt_hgb_shape"), 1)},
        "quant_int8": {"tx_s": 100000.0, "fused_tx_s": 120000.0,
                       "preq_tx_s": 150000.0, "batch": 65536,
                       "dtype": "int8"},
    }


def test_summary_is_small_and_carries_the_contract_keys():
    b = _load_bench()
    line = json.dumps(b.compact_summary(_full_result()))
    # well under the driver's ~2000-char tail even with prefix noise
    assert len(line) <= 1500, len(line)
    s = json.loads(line)
    for k in ("metric", "value", "unit", "vs_baseline", "platform"):
        assert k in s, k  # the driver contract
    assert s["device"]["kind"] == "TPU v5 lite"
    assert s["summary"] is True
    assert s["rest"]["tx_s"] == 347000.0
    assert s["rest"]["transport"] == "native"
    assert "clients" not in s["rest"]          # unbounded: dropped
    assert s["pipeline"]["p99_ms"] == 8.5
    assert s["zoo"] == {"logreg": 1000.0, "gbt": 2000.0,
                        "gbt_mxu": 3000.0, "gbt_hgb_shape": 4000.0}
    assert s["quant_int8"]["preq_tx_s"] == 150000.0
    assert "latency_batch" not in s            # grid: full record only


def test_section_failure_raises_instead_of_an_error_row(monkeypatch):
    """A section that fails must fail the run (non-zero exit through the
    uncaught exception), never become an ``{"error": ...}`` row or an
    ``"error: ..."`` rate in a record that exits 0."""
    import jax
    import numpy as np
    import pytest

    from ccfd_tpu.models import mlp
    from ccfd_tpu.ops import fused_mlp_q8, quant

    b = _load_bench()
    qp = quant.quantize_mlp(mlp.init(jax.random.PRNGKey(0)))

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(fused_mlp_q8, "fused_mlp_q8_score_preq", boom)
    with pytest.raises(RuntimeError, match="Mosaic"):
        b._preq_hop_rate(qp, np.zeros((32, 30), np.float32), 0.01)


def test_summary_survives_missing_sections():
    b = _load_bench()
    s = b.compact_summary({"metric": "m", "value": 1.0, "unit": "u",
                           "vs_baseline": 0.1, "platform": "cpu"})
    assert s["value"] == 1.0 and "rest" not in s and "zoo" not in s


def test_device_meter_attaches_section_rows():
    """The per-section device rows (h2d bytes delta + peak memory): a
    scorer built AFTER the meter installs itself stages through the
    process-default telemetry, and section() attaches the delta."""
    import numpy as np

    from ccfd_tpu.observability import device as device_mod
    from ccfd_tpu.serving.scorer import Scorer

    b = _load_bench()
    meter = b._DeviceMeter(attach_rows=True)
    try:
        s = Scorer(model_name="mlp", batch_sizes=(16,))
        assert s.telemetry is meter.tele
        s.warmup()
        meter.section(None)  # baseline reset past warmup
        s.score(np.zeros((16, 30), np.float32))
        row: dict = {}
        meter.section(row)
        assert row["device"]["h2d_bytes"] == 16 * 30 * 4
        assert "peak_device_memory_bytes" in row["device"]
        # next section starts from a fresh baseline
        row2: dict = {}
        meter.section(row2)
        assert row2["device"]["h2d_bytes"] == 0
    finally:
        device_mod.set_default(None)


def test_roofline_accounts_for_the_headline_hop():
    """The roofline block (VERDICT r4 items 4/5) must compute FLOP/row
    from the actual layer dims, scale achieved rates from the measured
    tx/s, and classify the bound — on a CPU run peaks are null and the
    classification falls back to host/h2d_wire, still labeled."""
    import jax
    import numpy as np

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import mlp
    from ccfd_tpu.serving.scorer import Scorer

    b = _load_bench()
    ds = synthetic_dataset(n=4096, fraud_rate=0.01, seed=0)
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    scorer = Scorer(model_name="mlp", params=params, batch_sizes=(1024,),
                    compute_dtype="bfloat16")
    scorer.warmup()
    r = b._bench_roofline(scorer, params, ds.X, 1024, 100_000.0,
                          {"tx_s": 50_000.0},
                          {"tx_s": 80_000.0, "preq_tx_s": 120_000.0})
    # 30->256->256->1 plus the normalizer: 2*(30*256+256*256+256) + 2*30
    assert r["flop_per_row"] == 147004
    hop = r["sections"]["scorer_hop"]
    assert hop["achieved_gflop_s"] == round(100_000.0 * 147004 / 1e9, 2)
    assert hop["bytes_per_row"] == 30 * np.dtype(r["wire_dtype"]).itemsize
    assert hop["wire_mb_s"] == round(
        100_000.0 * hop["bytes_per_row"] / 1e6, 2)
    # int8 wire rows: 30 int8 + one f32 scale
    assert r["sections"]["quant_int8_wire"]["bytes_per_row"] == 34
    assert r["sections"]["quant_int8_wire"]["tx_s"] == 120_000.0
    assert r["h2d"]["mb_s_measured"] > 0
    for k in ("host_prep_ms", "h2d_ms", "device_compute_ms"):
        assert r["split_ms"][k] >= 0
    if jax.default_backend() != "tpu":
        assert r["peaks"] is None
        assert "mfu_pct" not in hop
    assert r["bound"] in ("h2d_wire", "mxu", "hbm", "host")
