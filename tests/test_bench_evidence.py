# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""The benchmark evidence set as regression checks.

Reference analogue: ``scripts/pytorch_opt_linear_speedup_test.py`` —
performance claims live in runnable assertions, not prose. Every family
here runs on the virtual CPU mesh; what needs the chip is
``chip_smoke.py``'s to prove.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_mode(mode, extra_env, timeout):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["BENCH_MODE"] = mode
    env.update(extra_env)
    out = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    lines = [
        json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")
    ]
    return out, lines


PROVENANCE_KEYS = {
    "jax", "jaxlib", "cpu_model", "timing_method", "git_sha",
}


def _assert_provenance(lines):
    """Every bench artifact must open with the provenance block that
    makes round-over-round deltas attributable (jax/jaxlib versions,
    platform, CPU model, timing method, git SHA)."""
    prov = [l for l in lines if l.get("metric") == "provenance"]
    assert prov, "no provenance line in bench output"
    missing = PROVENANCE_KEYS - set(prov[0])
    assert not missing, f"provenance block missing {sorted(missing)}"
    assert prov[0]["jax"] and prov[0]["timing_method"]
    return prov[0]


def test_provenance_block_fields():
    """The provenance helper itself: every attribution field populated
    (unit-level; the subprocess tests check it reaches the artifacts)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)
    prov = bench_mod._provenance()
    assert PROVENANCE_KEYS <= set(prov)
    assert prov["cpu_model"], prov
    assert len(prov["git_sha"]) >= 7 or prov["git_sha"] == "unknown"


def test_scaling_mode_emits_flat_comm_evidence():
    """BENCH_MODE=scaling is self-contained evidence: one collective
    permute per one-peer step, wire bytes flat in N."""
    out, lines = _run_mode("scaling", {}, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    _assert_provenance(lines)
    comm = [l for l in lines if l.get("metric") == "one_peer_gossip_comm"]
    weak = [l for l in lines if l.get("metric") == "weak_scaling_gossip_step"]
    assert len(comm) >= 3 and weak, lines
    assert all(l["collective_permutes"] == 1 for l in comm), comm
    assert len({l["wire_bytes_per_worker"] for l in comm}) == 1, comm


def test_overlap_mode_emits_four_way_comparison():
    """BENCH_MODE=overlap emits the two-program / fused / fused+buckets
    / delayed comparison plus the bucket split and the static HLO
    overlap scan (small sizes; the timing assertion is exercised by the
    full-size bench run, not this smoke)."""
    out, lines = _run_mode(
        "overlap",
        {
            "BENCH_OVERLAP_DIM": "128", "BENCH_OVERLAP_LAYERS": "3",
            "BENCH_OVERLAP_BATCH": "8", "BENCH_STEPS": "2",
            "BENCH_WINDOWS": "2", "BENCH_OVERLAP_BUCKET_BYTES": "16384",
            "BENCH_ASSERT": "0",
        },
        timeout=1200,
    )
    assert out.returncode == 0, (out.stderr[-2000:], lines)
    steps = {
        l["variant"]: l for l in lines if l.get("metric") == "overlap_step"
    }
    assert set(steps) == {
        "two_program", "fused", "fused_buckets", "delayed"
    }, lines
    assert all("exposed_comm_ms" in l for l in steps.values())
    buckets = [l for l in lines if l.get("metric") == "overlap_buckets"]
    # 3 * 128 * 128 * 4B = 196 KiB over a 16 KiB cap -> many buckets
    assert buckets and buckets[0]["n_buckets"] > 1, lines
    hlo = {
        l["variant"]: l for l in lines if l.get("metric") == "overlap_hlo"
    }
    assert set(hlo) == {"fused", "fused_buckets", "delayed"}, lines
    for l in hlo.values():
        # every permute must be accounted for, async (TPU) or sync (CPU)
        assert l["async_pairs"] + l["sync_collective_permutes"] > 0, l
    # the delayed program's permutes consume only the carried buffer:
    # statically overlappable on any backend
    assert hlo["delayed"]["overlappable_permutes"] > 0, hlo["delayed"]
    timeline = [
        l for l in lines if l.get("metric") == "overlap_bucket_timeline"
    ]
    assert any(l["events"] for l in timeline), lines


def test_metrics_report_summarizes_jsonl(tmp_path):
    """tools/metrics_report.py digests a JSONL metrics file: min/max/last
    per series, snapshot count, stall count — the CLI a fleet operator
    points at BLUEFOG_METRICS_FILE output."""
    path = tmp_path / "run.jsonl"
    rows = [
        {"ts": 1.0, "metrics": {
            "bluefog.gossip.disagreement": {"type": "gauge", "value": 0.5},
            "bluefog.stalls": {"type": "counter", "value": 0},
            "bluefog.lat": {"type": "histogram", "count": 1, "sum": 2.0,
                            "min": 2.0, "max": 2.0, "last": 2.0},
        }},
        {"ts": 2.0, "metrics": {
            "bluefog.gossip.disagreement": {"type": "gauge", "value": 0.2},
            "bluefog.stalls": {"type": "counter", "value": 3},
        }},
    ]
    path.write_text(
        "\n".join(json.dumps(r) for r in rows) + "\nnot-json\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         str(path), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["snapshots"] == 2 and report["skipped_lines"] == 1
    assert report["stall_count"] == 3
    dis = report["series"]["bluefog.gossip.disagreement"]
    assert dis["min"] == 0.2 and dis["max"] == 0.5 and dis["last"] == 0.2
    assert report["series"]["bluefog.lat"]["last"] == 2.0
    # human-readable mode renders a table without crashing
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         str(path)],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out2.returncode == 0, out2.stderr
    assert "bluefog.gossip.disagreement" in out2.stdout
    assert "stalls:    3" in out2.stdout


def _check_metrics(lines):
    """METRICS_EVIDENCE.json (the committed BENCH_MODE=metrics output)
    carries the acceptance facts: <2% overhead at interval 10 and the
    bitwise on/off pin."""
    overhead = [l for l in lines if l.get("metric") == "metrics_overhead"]
    assert overhead, lines
    assert overhead[0]["bitwise_identical"] is True
    assert overhead[0]["overhead_pct"] < 2.0, overhead
    assert overhead[0]["interval"] == 10
    sample = [
        l for l in lines if l.get("metric") == "metrics_snapshot_sample"
    ]
    assert sample and "bluefog.gossip.disagreement" in sample[0]


@pytest.mark.chaos
def test_elastic_mode_emits_repair_evidence():
    """BENCH_MODE=elastic (small sizes): kill -> detect -> repair ->
    survivor-consensus evidence with the acceptance bounds asserted
    in-process (BENCH_ASSERT defaults on)."""
    out, lines = _run_mode(
        "elastic",
        {"BENCH_ELASTIC_DIM": "256", "BENCH_ELASTIC_STEPS": "30",
         "BENCH_ELASTIC_GRAD_STEPS": "8"},
        timeout=600,
    )
    assert out.returncode == 0, (out.stderr[-2000:], lines)
    _assert_provenance(lines)
    repair = [l for l in lines if l.get("metric") == "elastic_repair"]
    assert repair and repair[0]["steps_to_detect"] <= 1, lines
    assert repair[0]["steps_to_repair"] == 0
    cons = [l for l in lines if l.get("metric") == "elastic_consensus"]
    assert cons and cons[0]["post_repair_consensus_distance"] < 1e-3
    cache = [l for l in lines if l.get("metric") == "elastic_plan_cache"]
    assert cache and cache[0]["stale_commplan_dispatches"] == 0
    assert cache[0]["entries_with_live_token"] >= 1


def _check_elastic(lines):
    """ELASTIC_EVIDENCE.json (the committed BENCH_MODE=elastic output)
    carries the acceptance facts: bounded detection/repair, tight
    post-repair consensus distance vs the survivor oracle, zero stale
    CommPlan dispatches, live-token plan-cache keys — and the
    provenance block."""
    _assert_provenance(lines)
    repair = [l for l in lines if l.get("metric") == "elastic_repair"]
    assert repair, lines
    assert repair[0]["steps_to_detect"] <= 1
    assert repair[0]["steps_to_repair"] == 0
    cons = [l for l in lines if l.get("metric") == "elastic_consensus"]
    assert cons[0]["post_repair_consensus_distance"] < 1e-3
    cache = [l for l in lines if l.get("metric") == "elastic_plan_cache"]
    assert cache[0]["stale_commplan_dispatches"] == 0
    assert cache[0]["entries_with_live_token"] >= 1


SWEEP_REQUIRED_KEYS = {
    "payload_bytes", "cells_ms_per_step", "aa_baseline_ms",
    "aa_noise_pct", "auto_choice", "auto_chunks", "measured_best",
    "auto_tracks_best_within_noise", "rounds", "shortcut_rounds",
}


def _validate_sweep_lines(lines):
    """Schema of the plan-sweep evidence family: calibration line with
    measured constants, one sweep line per payload with every cell a
    positive measured time (degenerate cells must be FLAGGED and
    excluded from the winner comparison, never silently published)."""
    cal = [l for l in lines if l.get("metric") == "plan_calibration"]
    assert cal, "no plan_calibration line"
    assert cal[0]["alpha_us"] > 0 and cal[0]["beta_gbytes_per_s"] > 0
    assert 0.0 <= cal[0]["pipeline_eff"] <= 1.0
    assert cal[0]["source"] in ("measured-probe", "class-constants")
    sweep = [l for l in lines if l.get("metric") == "plan_sweep"]
    assert sweep, "no plan_sweep lines"
    for l in sweep:
        missing = SWEEP_REQUIRED_KEYS - set(l)
        assert not missing, (missing, l)
        degenerate = set(l.get("degenerate_cells", ()))
        for fam, ms in l["cells_ms_per_step"].items():
            assert ms > 0 or fam in degenerate, l
        if l["measured_best"] is not None:
            assert l["measured_best"] not in degenerate, l
        assert l["auto_chunks"] >= 1
    return cal[0], sweep


def test_plan_sweep_smoke_schema_and_bench_diff_check(tmp_path):
    """BENCH_MODE=plan sweep smoke: provenance line asserted, sweep
    schema validated, degenerate cells rejected from the winner pick —
    and the artifact round-trips through tools/bench_diff.py --check
    (self-diff), so future sweep artifact pairs stay machine-comparable
    by default."""
    out, lines = _run_mode(
        "plan",
        {
            "BENCH_STEPS": "2", "BENCH_WINDOWS": "1",
            "BENCH_PLAN_PAYLOAD_ELEMS": "1024",
            "BENCH_PLAN_SWEEP_BYTES": "65536,262144",
            "BENCH_PLAN_SWEEP_STEPS": "2",
            "BENCH_PLAN_SWEEP_WINDOWS": "1",
        },
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    _assert_provenance(lines)
    _validate_sweep_lines(lines)

    artifact = tmp_path / "sweep.json"
    artifact.write_text(
        "\n".join(json.dumps(l) for l in lines) + "\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    diff = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
         str(artifact), str(artifact), "--check", "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert diff.returncode == 0, diff.stderr
    report = json.loads(diff.stdout)
    assert not report["comparability_problems"], report
    paired = [c for c in report["cells"] if c["status"] == "paired"]
    assert paired, report
    # a self-diff must show zero delta everywhere
    for cell in paired:
        for d in cell["deltas"].values():
            assert d["delta_pct"] in (0.0, None), cell


def _check_plan_sweep(lines):
    """PLAN_SWEEP_EVIDENCE.json (the committed BENCH_MODE=plan payload
    sweep) carries the acceptance facts: measured calibration, the
    64 KiB -> 100 MiB sweep, and the auto chooser tracking the measured
    winner (within the disclosed A/A floor) at both sweep extremes —
    small payload on the min-round plan, large payload chunked."""
    _assert_provenance(lines)
    cal, sweep = _validate_sweep_lines(lines)
    assert cal["source"] == "measured-probe"
    sweep.sort(key=lambda l: l["payload_bytes"])
    assert sweep[0]["payload_bytes"] <= 64 * 1024
    assert sweep[-1]["payload_bytes"] >= 100 * 1024 * 1024
    for end in (sweep[0], sweep[-1]):
        assert end["auto_tracks_best_within_noise"] is True, end
    # the latency end stays on the min-round plan
    assert sweep[0]["auto_choice"] == "coloring_k1", sweep[0]


def _bench_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_row_validator_rejects_impossible_rows():
    """The row sanity validator (VERDICT #2): non-positive times and a
    fwd+bwd undercutting its own fwd are violations; plausible and
    degenerate-disclosed rows pass. run_flash wires this as
    reject+remeasure, so the r05 impossible rows cannot ship again."""
    bench = _bench_mod()
    ok = {
        "metric": "flash_attention_vs_dense",
        "flash_fwd_ms": 1.0, "flash_fwdbwd_ms": 3.0,
        "dense_fwd_ms": 2.0, "dense_fwdbwd_ms": 6.0,
    }
    assert bench.bench_row_problems(ok) == []
    impossible = dict(ok, dense_fwdbwd_ms=0.0)
    probs = bench.bench_row_problems(impossible)
    assert any("not a positive time" in p for p in probs)
    inverted = dict(ok, dense_fwdbwd_ms=1.5)  # fwdbwd < fwd
    probs = bench.bench_row_problems(inverted)
    assert any("cannot be faster" in p for p in probs)
    # rows already disclosed as degenerate are exempt (artifact, not
    # measurement)
    assert bench.bench_row_problems(dict(impossible, degenerate=True)) == []


def _check_attribution(lines):
    """ATTRIBUTION_EVIDENCE.json (the committed BENCH_MODE=attribution
    output) carries the acceptance facts: <=1% overhead at the default
    interval with the A/A control disclosed, the structural
    shared-cache-key pin, the bitwise on/off pin, a decomposition
    sample, the degraded-link advisory naming the injected edge, and
    the ambient-anchor line."""
    _assert_provenance(lines)
    overhead = [
        l for l in lines if l.get("metric") == "attribution_overhead"
    ]
    assert overhead, lines
    assert overhead[0]["overhead_pct"] <= 1.0
    assert "control_aa_pct" in overhead[0]
    assert overhead[0]["unsampled_program_shared"] is True
    assert overhead[0]["bitwise_identical"] is True
    sample = [
        l for l in lines if l.get("metric") == "attribution_sample"
    ]
    assert sample and sample[0]["comm_wire_ms"] > 0
    link = [
        l for l in lines if l.get("metric") == "attribution_degraded_link"
    ]
    assert link and link[0]["named_correctly"] is True
    assert link[0]["injected_edge"] in link[0]["edges_named"]
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def test_every_committed_evidence_keeps_anchor_contract():
    """New rounds' artifacts must carry the ambient anchor; this pins
    the contract on the one artifact this PR commits (older artifacts
    predate it — bench_diff reports them as lacking an anchor rather
    than failing)."""
    path = os.path.join(REPO, "ATTRIBUTION_EVIDENCE.json")
    lines = [
        json.loads(l) for l in open(path).read().splitlines()
        if l.startswith("{")
    ]
    anchors = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert len(anchors) == 1
    assert anchors[0]["dtype"] == "bfloat16" and anchors[0]["n"] >= 512


def test_bench_diff_classifies_ambient_vs_real(tmp_path):
    """tools/bench_diff.py consumes the anchor: a headline whose value
    moved but whose anchor-normalized vs_anchor held still is AMBIENT;
    one that survives normalization is REAL."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, tflops, value, windows=True):
        rows = [
            prov,
            {"metric": "ambient_anchor", "n": 512,
             "dtype": "bfloat16", "tflops": tflops},
            {"metric": "resnet50_bs64_imgs_per_sec_per_chip",
             "value": value, "unit": "imgs/sec/chip",
             "vs_anchor": round(value / tflops, 3),
             "median": value * 0.98, "min": value * 0.97,
             "windows": 8},
        ]
        path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        return str(path)

    # ambient: the host slowed 10% and the headline followed it
    a = artifact(tmp_path / "a.json", 100.0, 2800.0)
    b = artifact(tmp_path / "b.json", 90.0, 2520.0)
    rep = compare(a, b, [])
    assert rep["ambient_anchor_delta_pct"] == -10.0
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert cell["headline_delta_class"].startswith("ambient"), cell
    # real: the headline dropped 10% on an unmoved host
    c = artifact(tmp_path / "c.json", 100.0, 2520.0)
    rep2 = compare(a, c, [])
    cell2 = [c2 for c2 in rep2["cells"] if c2["status"] == "paired"][0]
    assert cell2["headline_delta_class"].startswith("real"), cell2


def _check_quant(lines):
    """QUANT_EVIDENCE.json (the committed BENCH_MODE=quant output)
    carries the acceptance facts: every wire tier measured on the same
    consensus problem, the >=2x int4-vs-int8 wire reduction with the
    scale sidecar priced in, int4_ef consensus no worse than int8's
    (within the disclosed multi-seed A/A spread), the push-sum
    mass-conservation check under the quantized window wire, and the
    provenance + ambient-anchor contract."""
    _assert_provenance(lines)
    tiers = {l["wire"]: l for l in lines if l.get("metric") == "quant_tier"}
    assert set(tiers) == {
        "fp32", "bf16", "int8", "int8_ef", "int4", "int4_ef",
    }, sorted(tiers)
    for name, t in tiers.items():
        assert t["wire_bytes_per_step"] > 0
        assert t["consensus_curve"], name
        assert t["final_consensus_median"] >= 0
    # byte ordering: int4 < int8 < bf16 < fp32; ef tiers match their base
    assert tiers["int4"]["wire_bytes_per_step"] < (
        tiers["int8"]["wire_bytes_per_step"]
    ) < tiers["bf16"]["wire_bytes_per_step"] < (
        tiers["fp32"]["wire_bytes_per_step"]
    )
    assert tiers["int4_ef"]["wire_bytes_per_step"] == (
        tiers["int4"]["wire_bytes_per_step"]
    )
    # quant-error telemetry covered the quantized tiers
    for name in ("int8", "int8_ef", "int4", "int4_ef"):
        assert tiers[name].get("quant_err_rms", 0) > 0, name
    summary = [l for l in lines if l.get("metric") == "quant_summary"]
    assert summary, lines
    s = summary[0]
    assert s["wire_reduction_int4_vs_int8"] >= 2.0, s
    assert s["int4_ef_no_worse_than_int8"] is True, s
    assert "aa_noise_pct" in s
    mass = [l for l in lines if l.get("metric") == "quant_window_mass"]
    assert mass and mass[0]["mass_conserved"] is True, lines
    assert mass[0]["max_mass_drift"] < mass[0]["mass_bound"]
    # fused wire kernels (BLUEFOG_WIRE_KERNELS): kernel-vs-composite
    # rows carry the bitwise pin and the scratch gate — fused temp
    # bytes BELOW the fp32 row for int8 AND int4 (the full-width
    # temporary never materializes), with the analytic fused model
    # re-derived against the committed columns
    from bluefog_tpu import scaling

    kern = {
        l["wire"]: l for l in lines if l.get("metric") == "quant_kernel"
    }
    assert set(kern) == {"int8", "int4"}, sorted(kern)
    for name, r in kern.items():
        assert r["bitwise_equal"] is True, r
        assert r["fused_below_fp32_row"] is True, r
        assert r["temp_bytes_fused"] < r["temp_bytes_fp32"], r
        assert r["temp_bytes_fused"] < r["temp_bytes_composite"], r
        # the composite row still stages the full-width reconstruction
        assert r["temp_bytes_composite"] >= 4 * r["payload_elems"], r
        assert r["temp_bytes_analytic_fused"] == (
            scaling.quantized_temporaries_bytes(
                r["payload_elems"], name, fused=True
            )
        ), r
        assert r["temp_bytes_analytic_composite"] == (
            scaling.quantized_temporaries_bytes(r["payload_elems"], name)
        ), r
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def _check_health(lines):
    """HEALTH_EVIDENCE.json (the committed BENCH_MODE=health output)
    carries the acceptance facts: measured consensus decay within the
    disclosed tolerance of the spectral prediction on ring AND Exp2
    with the Exp2-mixes-faster ordering, sampled-health overhead <=1%
    with the A/A control and the structural + bitwise pins, the
    push-sum lane matching its numpy oracle under a dead rank, and the
    chaos scenario where ``mixing_degraded`` names the injected edge —
    plus provenance and the ambient anchor."""
    _assert_provenance(lines)
    decay = {
        l["topology"]: l for l in lines
        if l.get("metric") == "health_decay"
    }
    assert set(decay) == {"ring", "exp2"}, sorted(decay)
    for name, l in decay.items():
        assert l["within_tolerance"] is True, l
        assert 0 < l["predicted_rate"] < 1
        assert 0 < l["measured_rate"] < 1
        assert l["tolerance"] <= 0.2  # the disclosed bound stays tight
        assert l["time_to_eps_steps"] > 0
    order = [
        l for l in lines if l.get("metric") == "health_decay_ordering"
    ]
    assert order and order[0]["exp2_mixes_faster_than_ring"] is True
    fleet = [l for l in lines if l.get("metric") == "health_fleet"]
    assert fleet, lines
    assert fleet[0]["lane_vs_oracle_max_err"] < 1e-3
    assert fleet[0]["minmax_exact_over_live"] is True
    assert fleet[0]["mean_rel_err_vs_true"] < 0.05
    assert fleet[0]["dead_ranks"], "oracle must cover a dead rank"
    overhead = [
        l for l in lines if l.get("metric") == "health_overhead"
    ]
    assert overhead, lines
    assert overhead[0]["overhead_pct"] <= 1.0
    assert "control_aa_pct" in overhead[0]
    assert overhead[0]["unsampled_program_shared"] is True
    assert overhead[0]["bitwise_identical"] is True
    mix = [
        l for l in lines
        if l.get("metric") == "health_mixing_degraded"
    ]
    assert mix and mix[0]["named_correctly"] is True
    assert mix[0]["injected_edge"] in mix[0]["edges_named"]
    assert mix[0]["degraded_efficiency"] < mix[0]["healthy_efficiency"]
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def test_bench_diff_health_columns_are_tooling_gained(tmp_path):
    """The health evidence adds mixing-observatory columns
    (predicted/measured rate, efficiency) to cells; against a
    pre-health artifact their one-sided appearance must read as
    tooling-gained-a-column, never a timing-harness break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_health_cols):
        row = {
            "metric": "gossip_step", "n_workers": 8,
            "ms_per_step": 10.0, "median": 10.1, "min": 9.9,
        }
        if with_health_cols:
            row["predicted_rate"] = 0.5
            row["measured_rate"] = 0.51
            row["mixing_efficiency"] = 0.97
        path.write_text(
            json.dumps(prov) + "\n" + json.dumps(row) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def test_bench_diff_wire_columns_are_tooling_gained(tmp_path):
    """The quantized-wire evidence adds wire-byte accounting columns to
    existing cells; against a pre-quant artifact their one-sided
    appearance must read as tooling-gained-a-column (cell stays
    comparable), not a timing-harness break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_wire_cols):
        row = {
            "metric": "gossip_step", "n_workers": 8,
            "ms_per_step": 10.0, "median": 10.1, "min": 9.9,
        }
        if with_wire_cols:
            row["wire_bytes_per_step"] = 12384
            row["effective_compression_ratio"] = 3.97
        path.write_text(
            json.dumps(prov) + "\n" + json.dumps(row) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def test_bench_diff_wire_kernel_columns_are_tooling_gained(tmp_path):
    """The fused-wire-kernel evidence (quant_kernel rows +
    kernel-vs-composite scratch/step-time columns) against a pre-kernel
    QUANT_EVIDENCE artifact must read as tooling-gained
    (WIRE_KERNEL_DERIVED), never a comparability break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare, WIRE_KERNEL_DERIVED, TOOLING_DERIVED

    assert WIRE_KERNEL_DERIVED <= TOOLING_DERIVED

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_kernel_evidence):
        tier = {
            "metric": "quant_tier", "wire": "int4", "n_workers": 8,
            "final_consensus_median": 13.0,
        }
        rows = [prov, tier]
        if with_kernel_evidence:
            # the columns on an existing cell AND the new metric rows
            tier = dict(tier, step_time_fused_us=2653.8,
                        temp_bytes_fused=6344)
            rows = [prov, tier, {
                "metric": "quant_kernel", "wire": "int4",
                "temp_bytes_composite": 20640, "temp_bytes_fused": 6344,
                "temp_bytes_fp32": 16384, "bitwise_equal": True,
            }]
        path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def _check_autotune(lines):
    """AUTOTUNE_EVIDENCE.json (the committed BENCH_MODE=autotune
    output) carries the acceptance facts: the injected degraded link
    detected through the real doctor advisory stream with the decision
    record naming it in its trigger set, the migrated topology
    excluding the blamed edge with zero stale dispatches and the
    measured wire cost recovering, mixing efficiency recovering past
    the gate in the deterministic lossy-link replay, controller
    overhead <=1% at the default interval with the A/A control and
    structural + bitwise pins, the dry-run pass recording full history
    with zero migrations, and the audit trail round-tripping through
    every surface — plus provenance and the ambient anchor."""
    _assert_provenance(lines)
    chaos = [l for l in lines if l.get("metric") == "autotune_chaos"]
    assert chaos, lines
    assert chaos[0]["detected_by_doctor"] is True
    assert chaos[0]["injected_edge"] in chaos[0]["edges_named"]
    assert chaos[0]["decision_action"] == "swap"
    assert chaos[0]["trigger_names_edge"] is True
    assert chaos[0]["migrated_excludes_edge"] is True
    assert chaos[0]["edge_weight_after"] < chaos[0]["edge_weight_before"]
    assert chaos[0]["comm_wire_recovery_ratio"] >= 2.0
    assert chaos[0]["stale_dispatches"] == 0
    assert chaos[0]["training_state_finite"] is True
    rec = [
        l for l in lines
        if l.get("metric") == "autotune_mixing_recovery"
    ]
    assert rec, lines
    assert rec[0]["advisory_fired"] is True
    assert rec[0]["advisory_names_edge"] is True
    assert rec[0]["efficiency_recovered"] >= 0.9
    assert rec[0]["efficiency_degraded"] < rec[0]["efficiency_recovered"]
    assert rec[0]["recovered_step_ratio"] >= 2.0
    assert rec[0]["migrated_excludes_edge"] is True
    assert "calibration" in rec[0]  # the sim channel is disclosed
    dry = [l for l in lines if l.get("metric") == "autotune_dry_run"]
    assert dry, lines
    assert dry[0]["migrations_zero"] is True
    assert dry[0]["swaps"] == 0
    assert dry[0]["decisions"] >= 1
    assert dry[0]["actions"] == ["dry_run_swap"]
    assert dry[0]["candidates_recorded"] is True
    audit = [l for l in lines if l.get("metric") == "autotune_audit"]
    assert audit, lines
    assert audit[0]["flight_side_table_has_swap"] is True
    assert audit[0]["jsonl_reconstruction_matches"] is True
    assert audit[0]["dump_reconstruction_matches"] is True
    assert audit[0]["report_joins_verification"] is True
    assert audit[0]["fleet_block"].get("swaps", 0) >= 1
    overhead = [
        l for l in lines if l.get("metric") == "autotune_overhead"
    ]
    assert overhead, lines
    assert overhead[0]["overhead_pct"] <= 1.0
    assert "control_aa_pct" in overhead[0]
    assert overhead[0]["unsampled_program_shared"] is True
    assert overhead[0]["bitwise_identical"] is True
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def test_bench_diff_autotune_columns_are_tooling_gained(tmp_path):
    """The autotune evidence adds controller-bookkeeping columns
    (decision counts, predicted objectives, recovery ratios) to
    cells; against a pre-autotune artifact their one-sided appearance
    must read as tooling-gained-a-column, never a timing-harness
    break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_autotune_cols):
        row = {
            "metric": "gossip_step", "n_workers": 8,
            "ms_per_step": 10.0, "median": 10.1, "min": 9.9,
        }
        if with_autotune_cols:
            row["decisions"] = 3
            row["swaps"] = 1
            row["rollbacks"] = 0
            row["recovered_step_ratio"] = 17.7
        path.write_text(
            json.dumps(prov) + "\n" + json.dumps(row) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def _check_async(lines):
    """ASYNC_EVIDENCE.json (the committed BENCH_MODE=async output)
    carries the acceptance facts: one rank compute-dilated 10x
    collapses synchronous fleet throughput to ~1/dilation while the
    async lane's measured participation stays within ~1/N of nominal
    (same artifact, same problem); convergence within tolerance of the
    synchronous baseline; exact push-sum mass conservation per wire
    tier (fp32/int8_ef/int4_ef) under random cadences; the
    bounded-staleness gate engaging with an age histogram and the
    ``async_staleness`` advisory naming the slow rank; and the
    async-off dispatch pinned bitwise to the current optimizer path —
    plus provenance and the ambient anchor."""
    _assert_provenance(lines)
    strag = [l for l in lines if l.get("metric") == "async_straggler"]
    assert strag, lines
    s = strag[0]
    assert s["within_1_over_n"] is True
    assert s["sync_collapse"] is True
    assert s["fleet_ratio_async"] >= 1.0 - 1.5 / s["workers"]
    assert s["fleet_ratio_sync"] <= 1.5 / s["dilation"]
    assert s["dilation"] >= 10
    assert 0 <= s["slow_rank"] < s["workers"]
    assert "simulated" in s["dilation_model"]
    assert s["measured_async_tick_ms"] > 0
    assert s["measured_sync_step_ms"] > 0
    conv = [l for l in lines if l.get("metric") == "async_convergence"]
    assert conv, lines
    assert conv[0]["within_tolerance"] is True
    assert conv[0]["dist_to_opt_async"] <= (
        conv[0]["tolerance_factor"] * conv[0]["dist_to_opt_sync"] + 1e-3
    )
    mass = [l for l in lines if l.get("metric") == "async_mass"]
    assert mass, lines
    assert mass[0]["conserved_all_tiers"] is True
    assert set(mass[0]["tiers"]) == {"fp32", "int8_ef", "int4_ef"}
    for tier, rec in mass[0]["tiers"].items():
        assert rec["conserved"] is True, (tier, rec)
        assert rec["mass_drift"] < rec["bound"], (tier, rec)
    gate = [
        l for l in lines if l.get("metric") == "async_staleness_gate"
    ]
    assert gate, lines
    g = gate[0]
    assert g["gate_engaged"] is True
    assert g["advisory_names_slow_rank"] is True
    assert g["age_max"] > g["max_age"]
    assert g["age_hist"], g
    assert any(int(a) > g["max_age"] for a in g["age_hist"])
    assert g["fresh_edges_within_bound"] <= g["max_age"]
    assert all(
        int(s0) == strag[0]["slow_rank"] for s0, _d in g["advisory_edges"]
    )
    off = [l for l in lines if l.get("metric") == "async_off_bitwise"]
    assert off, lines
    assert off[0]["bitwise_identical"] is True
    assert off[0]["dispatch_path_shared"] is True
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def test_bench_diff_async_columns_are_tooling_gained(tmp_path):
    """The async evidence adds cadence-replay bookkeeping columns
    (participation ratios, mass-drift pins, gate statistics); against
    a pre-async artifact their one-sided appearance must read as
    tooling-gained-a-column, never a timing-harness break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_async_cols):
        row = {
            "metric": "gossip_step", "n_workers": 8,
            "ms_per_step": 10.0, "median": 10.1, "min": 9.9,
        }
        if with_async_cols:
            row["fleet_ratio_async"] = 0.8875
            row["fleet_ratio_sync"] = 0.1
            row["mass_drift_max"] = 1.4e-5
            row["age_max"] = 9
        path.write_text(
            json.dumps(prov) + "\n" + json.dumps(row) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def _check_staleness(lines):
    """STALENESS_EVIDENCE.json (the committed BENCH_MODE=staleness
    output) carries the acceptance facts: synchronous-path delivered
    age identically 0 with the lane self-check green and the lineage
    sidecar priced by ``scaling.wire_payload_bytes``; delayed-path
    steady-state age 1 with the topology-swap reseed transition;
    the age-discounted mixing correction shrinking the health plane's
    predicted-vs-measured residual on a delayed run; observatory
    overhead <=1% at the default interval with the A/A control and the
    structural + bitwise pins; and the chaos scenario where an
    injected per-edge stall produces exactly the expected age spike
    and ``staleness_breach`` names the edge — plus provenance and the
    ambient anchor."""
    _assert_provenance(lines)
    sync = [l for l in lines if l.get("metric") == "staleness_sync"]
    assert sync, lines
    assert sync[0]["ages_all_zero"] is True
    assert sync[0]["lane_selfcheck_ok"] is True
    assert sync[0]["sidecar_priced_in_wire_payload_bytes"] is True
    assert sync[0]["lineage_tag_bytes"] == 12
    assert sync[0]["lane_wire_bytes_total"] > 0
    delayed = [
        l for l in lines if l.get("metric") == "staleness_delayed"
    ]
    assert delayed, lines
    assert delayed[0]["seed_age_zero"] is True
    assert delayed[0]["steady_state_age_one"] is True
    assert delayed[0]["swap_transition_age_zero"] is True
    residual = [
        l for l in lines if l.get("metric") == "staleness_residual"
    ]
    assert residual, lines
    assert residual[0]["residual_shrinks"] is True
    assert residual[0]["residual_age_adjusted"] < \
        residual[0]["residual_raw"]
    assert residual[0]["age_mean"] is not None
    overhead = [
        l for l in lines if l.get("metric") == "staleness_overhead"
    ]
    assert overhead, lines
    assert overhead[0]["overhead_pct"] <= 1.0
    assert "control_aa_pct" in overhead[0]
    assert overhead[0]["unsampled_program_shared"] is True
    assert overhead[0]["bitwise_identical"] is True
    chaos = [l for l in lines if l.get("metric") == "staleness_chaos"]
    assert chaos, lines
    assert chaos[0]["named_correctly"] is True
    assert chaos[0]["spike_matches_hold"] is True
    assert chaos[0]["other_edges_age_zero"] is True
    assert chaos[0]["lane_selfcheck_ok"] is True
    assert chaos[0]["injected_edge"] in chaos[0]["edges_named"]
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def _check_shard(lines):
    """SHARD_EVIDENCE.json (the committed BENCH_MODE=shard output)
    carries the acceptance facts: measured per-rank Adam state bytes at
    1/N (+ the disclosed 512-alignment slack) on an 8-worker mesh, for
    a model whose REPLICATED state exceeds the simulated per-chip
    budget the sharded run trains under; the sharded trajectory
    matching both the replicated path and the numpy Adam oracle (and
    the ZeRO-2 reduce-scatter run inside the SAME envelope); step
    time within the disclosed A/A noise floor of unsharded; the
    BLUEFOG_SHARD=0 bitwise pin with zero shard-tagged cache keys; and
    the ZeRO-2 gradient-wire row (measured reduced-gradient bytes at
    ~1/N with disclosed pad slack, scatter+gather <= allreduce+gather,
    per-tier scatter wire at the exact block-scale ratios) — plus
    provenance and the ambient anchor."""
    _assert_provenance(lines)
    mem = [l for l in lines if l.get("metric") == "shard_memory"]
    assert mem, lines
    m = mem[0]
    assert m["workers"] == 8
    assert m["replicated_exceeds_budget"] is True
    assert m["sharded_fits_budget"] is True
    assert m["state_bytes_sharded"] <= m["budget_bytes"]
    assert m["state_bytes_replicated"] > m["budget_bytes"]
    # 1/N + bucket-padding slack: the slot/dim ratio IS that bound
    bound = (
        m["state_bytes_replicated"] * (m["slot_elems"] / m["dim"]) * 1.02
        + 4096
    )
    assert m["state_bytes_sharded"] <= bound, (m, bound)
    assert m["shard_ratio"] < 0.2  # well under 1/8 + slack at N=8
    assert m["loss_end"] < 0.5 * m["loss_start"]
    assert m["replica_spread"] == 0.0
    assert m["gather_bytes_per_step"] > 0
    traj = [l for l in lines if l.get("metric") == "shard_trajectory"]
    assert traj, lines
    assert traj[0]["sharded_matches_replicated"] is True
    assert traj[0]["sharded_matches_numpy_oracle"] is True
    assert traj[0]["traj_max_dev"] <= traj[0]["tol"]
    # ZeRO-2 (reduce-scatter gradient leg) sits inside the SAME pin
    # envelope — the scatter changed the wire, not the trajectory
    assert traj[0]["zero2_matches_replicated"] is True
    assert traj[0]["zero2_matches_numpy_oracle"] is True
    assert traj[0]["zero2_max_dev"] <= traj[0]["tol"]
    t = [l for l in lines if l.get("metric") == "shard_step_time"]
    assert t, lines
    assert t[0]["within_noise"] is True
    assert t[0]["aa_noise_pct"] >= 0  # the floor is disclosed
    assert abs(t[0]["delta_pct"]) <= t[0]["noise_bound_pct"]
    off = [l for l in lines if l.get("metric") == "shard_off_pin"]
    assert off, lines
    assert off[0]["bitwise_identical"] is True
    assert off[0]["shard_tagged_cache_keys"] == 0
    gw = [l for l in lines if l.get("metric") == "shard_grad_wire"]
    assert gw, lines
    g = gw[0]
    # measured reduced-gradient footprint is exactly slot/dim of
    # replicated (both real f32 buffers); the ratio is ~1/N plus the
    # DISCLOSED pad slack
    assert g["grad_bytes_sharded_measured"] * g["dim"] == (
        g["grad_bytes_replicated_measured"] * g["slot_elems"]
    ), g
    assert g["grad_ratio_measured"] <= (
        1.0 / g["workers"] + g["grad_pad_ratio"] + 1e-6
    ), g
    assert g["grad_pad_ratio"] >= 0
    # the wire claim: the ZeRO-2 leg never ships more than the baseline
    assert g["wire_le_baseline"] is True
    assert g["scatter_plus_gather"] <= g["allreduce_plus_gather"], g
    assert g["scatter_bytes_per_step"] < g["allreduce_bytes_per_step"], g
    # quantized scatter tiers at the EXACT block-scale ratios (slots
    # are 512-grid multiples, so 516/2048 and 258/2048 are exact)
    tiers = g["tiers"]
    assert tiers["int8"]["ratio_vs_fp32"] == round(516 / 2048, 6), g
    assert tiers["int4"]["ratio_vs_fp32"] == round(258 / 2048, 6), g
    assert tiers["int8_ef"]["ratio_vs_fp32"] == (
        tiers["int8"]["ratio_vs_fp32"]
    ), g
    assert tiers["int4_ef"]["ratio_vs_fp32"] == (
        tiers["int4"]["ratio_vs_fp32"]
    ), g
    assert tiers["bf16"]["ratio_vs_fp32"] == 0.5, g
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def test_bench_diff_shard_columns_are_tooling_gained(tmp_path):
    """The shard evidence adds state-byte/layout accounting columns;
    against a pre-shard artifact their one-sided appearance must read
    as tooling-gained-a-column, never a timing-harness break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_shard_cols):
        row = {
            "metric": "gossip_step", "n_workers": 8,
            "ms_per_step": 10.0, "median": 10.1, "min": 9.9,
        }
        if with_shard_cols:
            row["state_bytes_replicated"] = 2097164
            row["state_bytes_sharded"] = 266244
            row["shard_ratio"] = 0.127
            row["gather_bytes_per_step"] = 931840
        path.write_text(
            json.dumps(prov) + "\n" + json.dumps(row) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell

def _check_memory(lines):
    """MEMORY_EVIDENCE.json (the committed BENCH_MODE=memory output)
    carries the acceptance facts: the observatory's live-array census
    of the optimizer state reconciling with the analytic
    ``scaling.optimizer_state_bytes`` model within the disclosed
    tolerance for BOTH ``BLUEFOG_SHARD=0/1``, with the measured
    sharded/replicated ratio consistent with SHARD_EVIDENCE's x0.127
    at N=8; the measured quantized-wire temporary-bytes column at the
    PR-8 payload width (the full-width f32 temporary materializes, and
    the quantized scratch exceeds the exact path's — the ROADMAP-2
    fusion before-baseline); observatory overhead <=1% at the default
    interval with the A/A control, the compile-nothing structural pin
    and the bitwise pin; and the memory_pressure advisory firing under
    a simulated budget with the shard-recommendation hint — plus
    provenance (now carrying peak_rss_bytes) and the ambient anchor."""
    prov = _assert_provenance(lines)
    assert prov.get("peak_rss_bytes", 0) > 0, prov
    rec = [l for l in lines if l.get("metric") == "memory_reconcile"]
    assert rec, lines
    r = rec[0]
    assert r["both_within_tolerance"] is True
    assert r["replicated_rel_err"] <= r["tolerance"]
    assert r["sharded_rel_err"] <= r["tolerance"]
    assert r["ratio_consistent_with_shard_evidence"] is True
    assert abs(r["measured_shard_ratio"] - 0.127) <= 0.02
    assert r["sharded_measured_bytes"] < r["replicated_measured_bytes"]
    temps = {
        l["wire"]: l for l in lines
        if l.get("metric") == "memory_wire_temps"
    }
    assert {"fp32", "int8", "int4"} <= set(temps), sorted(temps)
    for name in ("int8", "int4"):
        t = temps[name]
        assert t["full_width_temporary_materializes"] is True, t
        assert t["temp_bytes_measured"] >= t["full_width_bytes"], t
        assert t["temp_bytes_measured"] > (
            temps["fp32"]["temp_bytes_measured"]
        ), t
        # the analytic staging model re-derived arithmetically
        # (scaling.quantized_temporaries_bytes: f32 dequant + int8
        # staging + the int4 packed-nibble copy over the 512-padded
        # payload) — a silent regression in the block math cannot
        # ship into the committed baseline
        n = t["payload_elems"]
        padded = -(-n // 512) * 512
        expect = 4 * padded + padded + (
            padded // 2 if name == "int4" else 0
        )
        assert t["temp_bytes_analytic"] == expect, t
    summary = [
        l for l in lines if l.get("metric") == "memory_wire_summary"
    ]
    assert summary and summary[0]["all_full_width"] is True
    assert summary[0]["quantized_scratch_exceeds_exact"] is True
    overhead = [
        l for l in lines if l.get("metric") == "memory_overhead"
    ]
    assert overhead, lines
    assert overhead[0]["overhead_pct"] <= 1.0
    assert "control_aa_pct" in overhead[0]
    assert overhead[0]["unsampled_program_shared"] is True
    assert overhead[0]["observatory_cache_entries"] == 0
    assert overhead[0]["bitwise_identical"] is True
    pressure = [
        l for l in lines if l.get("metric") == "memory_pressure"
    ]
    assert pressure, lines
    assert pressure[0]["advisory_fired"] is True
    assert pressure[0]["shard_hint"] is True
    assert pressure[0]["headroom_bytes"] < 0
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def _check_fleetscale(lines):
    """FLEETSCALE_EVIDENCE.json (the committed BENCH_MODE=fleetscale
    output) carries the acceptance facts: per-membership-event repair
    cost sublinear in N over the {128..1024} sweep (growth exponent
    < 1) with the dense baseline extrapolated by a DISCLOSED power-law
    model rather than run at fleet scale; the 10% simultaneous
    rank-loss storm at N=1024 repaired with zero stale dispatches
    under full edge auditing (churn advisory filed, exact survivor
    count); bounded controller decision latency at N=1024 with every
    candidate scored by the sparse spectral engine; and the
    sparse-vs-dense SLEM agreement spot check at the routing boundary
    — plus provenance and the ambient anchor."""
    _assert_provenance(lines)
    scaling = [
        l for l in lines if l.get("metric") == "fleetscale_event_scaling"
    ]
    assert scaling, lines
    s = scaling[0]
    assert s["sublinear"] is True
    assert s["growth_exponent"] < 1.0
    assert {c["n"] for c in s["cells"]} >= {128, 256, 512, 1024}
    assert "dense_extrapolation_model" in s
    assert s["dense_at_1024_ms_extrapolated"] > s["sparse_at_1024_ms"]
    assert s["speedup_at_1024_extrapolated"] > 10.0
    storm = [l for l in lines if l.get("metric") == "fleetscale_storm"]
    assert storm, lines
    st = storm[0]
    assert st["n"] == 1024
    assert st["stale_dispatches"] == 0
    assert st["live_after"] == st["n"] - st["killed"]
    assert st["killed"] == round(st["n"] * st["fraction"])
    assert "fleet_churn" in st["advisories"]
    decision = [
        l for l in lines if l.get("metric") == "fleetscale_decision"
    ]
    assert decision, lines
    d = decision[0]
    assert d["decision_ms"] <= d["bound_ms"]
    for name, cand in d["candidates"].items():
        assert cand["spectral"]["engine"] == "sparse", (name, cand)
    agree = [
        l for l in lines if l.get("metric") == "fleetscale_agreement"
    ]
    assert agree, lines
    assert agree[0]["worst_abs_diff"] <= agree[0]["tolerance"]
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def _check_federate(lines):
    """FEDERATE_EVIDENCE.json (the committed BENCH_MODE=federate
    output) carries the acceptance facts of the two-level ICI/DCN
    fabric: the spectrally-chosen DCN period's predicted composed
    consensus rate agreeing with the host-measured rate within the
    disclosed tolerance; the >= 8x cross-pod wire-byte cut against the
    strongest flat opponent at the matched measured rate; whole-pod
    loss repaired as ONE event with zero stale dispatches and the
    gateway re-election on record; and the live 2-pod dispatch whose
    per-leg federation counters reconcile with the total — plus
    provenance (with the per-link-class calibration echoed) and the
    ambient anchor."""
    _assert_provenance(lines)
    prov = [l for l in lines if l.get("metric") == "provenance"][0]
    classes = prov.get("calibration_link_classes", {})
    assert {"ici", "dcn"} <= set(classes), prov
    for cls, cal in classes.items():
        assert cal["link_class"] == cls, cal
        assert cal["alpha_s"] > 0 and cal["beta_bytes_per_s"] > 0, cal
    period = [l for l in lines if l.get("metric") == "federate_period"]
    assert period, lines
    p = period[0]
    assert p["met"] is True
    assert p["abs_err"] <= p["tolerance"], p
    assert any(
        row["period"] == p["chosen_period"] for row in p["table"]
    ), p
    assert p["predicted_rate"] <= p["target_rate"], p
    wire = [l for l in lines if l.get("metric") == "federate_wire"]
    assert wire, lines
    w = wire[0]
    assert w["dcn_cut_ratio_matched"] >= 8.0, w
    # the flat opponent must really be at least as strong at the
    # matched cadence — otherwise the cut ratio compares against a
    # weaker consensus contract
    assert (
        w["measured_rate_flat_matched"]
        <= w["measured_rate_fed"] + 1e-6
    ), w
    assert w["flat_gossip_every"] >= 1, w
    pod = [l for l in lines if l.get("metric") == "federate_podloss"]
    assert pod, lines
    pl = pod[0]
    assert pl["repair_events"] == 1, pl
    assert pl["stale_dispatches"] == 0, pl
    assert pl["loss_class"] == "pod_loss", pl
    assert pl["pods_lost"] == [pl["pod_lost"]], pl
    assert pl["live_after"] == pl["n"] - pl["ranks_lost"], pl
    disp = [l for l in lines if l.get("metric") == "federate_dispatch"]
    assert disp, lines
    d = disp[0]
    assert d["ici_wire_bytes"] > 0 and d["dcn_wire_bytes"] > 0, d
    assert d["total_wire_bytes"] == (
        d["ici_wire_bytes"] + d["dcn_wire_bytes"]
    ), d
    assert d["mean_preserved"] is True, d
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


def test_bench_diff_federate_columns_are_tooling_gained(tmp_path):
    """The federation evidence columns (composed-rate predictions,
    per-leg byte totals, matched-rate cut ratios) against a
    pre-federation artifact must read as tooling-gained
    (FEDERATE_DERIVED), never a comparability break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare, FEDERATE_DERIVED, TOOLING_DERIVED

    assert FEDERATE_DERIVED <= TOOLING_DERIVED

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_federate):
        rows = [prov, {
            "metric": "health_decay", "topology": "ring",
            "n_workers": 8, "predicted_rate": 0.8,
        }]
        if with_federate:
            rows.append({
                "metric": "federate_wire", "n": 16,
                "dcn_cut_ratio_matched": 39.7,
                "fed_dcn_bytes_per_step": 132096.0,
            })
        path.write_text(
            "\n".join(json.dumps(r) for r in rows) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def test_bench_diff_fleetscale_columns_are_tooling_gained(tmp_path):
    """The fleet-scale evidence columns (event costs, exponent fits,
    decision latency) against a pre-fleetsim artifact must read as
    tooling-gained (FLEETSCALE_DERIVED), never a comparability
    break."""
    sys.path.insert(0, REPO)
    from tools.bench_diff import compare, FLEETSCALE_DERIVED, TOOLING_DERIVED

    assert FLEETSCALE_DERIVED <= TOOLING_DERIVED

    prov = {
        "metric": "provenance", "jax": "1", "jaxlib": "1",
        "cpu_model": "x", "timing_method": "t", "git_sha": "a",
    }

    def artifact(path, with_fleetscale):
        rows = [prov, {
            "metric": "health_decay", "topology": "ring",
            "n_workers": 8, "predicted_rate": 0.8,
        }]
        if with_fleetscale:
            rows.append({
                "metric": "fleetscale_storm", "n": 1024,
                "stale_dispatches": 0, "worst_event_ms": 0.28,
            })
        path.write_text(
            "\n".join(json.dumps(r) for r in rows) + "\n"
        )
        return str(path)

    old = artifact(tmp_path / "old.json", False)
    new = artifact(tmp_path / "new.json", True)
    rep = compare(old, new, [])
    assert not rep["comparability_problems"], rep
    cell = [c for c in rep["cells"] if c["status"] == "paired"][0]
    assert not cell.get("harness_change"), cell
    assert cell["verdict"].startswith("comparable"), cell


def _check_slo(lines):
    """SLO_EVIDENCE.json (the committed BENCH_MODE=slo output) carries
    the acceptance facts: the fault paging within the documented
    sample bound with a clean A/A, the slow-window/fast-window/hygiene
    separation on the ramp, the canary naming exactly the injected
    edge, sampled-SLO overhead <=1% with the A/A control and the
    structural + bitwise pins, and the N=1024 churn-storm burn math
    exact against the numpy oracle — plus provenance and the ambient
    anchor."""
    _assert_provenance(lines)
    page = [l for l in lines if l.get("metric") == "slo_page_bound"]
    assert page, lines
    assert page[0]["paged_within_bound"] is True
    assert page[0]["samples_to_page"] <= page[0]["page_sample_bound"]
    assert page[0]["warmup_false_alarms"] == 0
    assert page[0]["aa_false_alarms"] == 0
    assert page[0]["aa_steps"] >= 500
    ramp = [l for l in lines if l.get("metric") == "slo_slow_ramp"]
    assert ramp, lines
    assert ramp[0]["slow_window_fired"] is True
    assert ramp[0]["fast_window_silent"] is True
    assert ramp[0]["hygiene_streak_armed"] is False
    canary = [l for l in lines if l.get("metric") == "slo_canary"]
    assert canary, lines
    assert canary[0]["probe_elems"] == 512
    assert canary[0]["clean_ok"] is True
    assert canary[0]["clean_max_dev"] <= canary[0]["tolerance"]
    assert canary[0]["lossy_ok"] is False
    assert canary[0]["named_correctly"] is True
    assert canary[0]["injected_edge"] in canary[0]["edges_named"]
    overhead = [l for l in lines if l.get("metric") == "slo_overhead"]
    assert overhead, lines
    assert overhead[0]["overhead_pct"] <= 1.0
    assert "control_aa_pct" in overhead[0]
    assert overhead[0]["unsampled_program_shared"] is True
    assert overhead[0]["bitwise_identical"] is True
    assert overhead[0]["canary_programs"] >= 1
    storm = [l for l in lines if l.get("metric") == "slo_fleet_storm"]
    assert storm, lines
    assert storm[0]["fleet_n"] >= 1024
    assert storm[0]["max_burn_err_vs_oracle"] == 0.0
    assert storm[0]["max_budget_err_vs_oracle"] == 0.0
    assert storm[0]["paged_within_bound"] is True
    catalog = [l for l in lines if l.get("metric") == "slo_catalog"]
    assert catalog and len(catalog[0]["objectives"]) >= 8
    anchor = [l for l in lines if l.get("metric") == "ambient_anchor"]
    assert anchor and anchor[0]["tflops"] > 0


# -- the committed-evidence sweep ---------------------------------------------
#
# One parametrized test over EVERY committed evidence artifact: each
# family contributes its filename and a schema-check function, so the
# next evidence family is schema-checked by adding ONE row here — the
# per-file test boilerplate (exists + parse + provenance) lives in one
# place instead of ten copies.

EVIDENCE_CHECKS = {
    "METRICS_EVIDENCE.json": _check_metrics,
    "ELASTIC_EVIDENCE.json": _check_elastic,
    "PLAN_SWEEP_EVIDENCE.json": _check_plan_sweep,
    "ATTRIBUTION_EVIDENCE.json": _check_attribution,
    "QUANT_EVIDENCE.json": _check_quant,
    "HEALTH_EVIDENCE.json": _check_health,
    "SLO_EVIDENCE.json": _check_slo,
    "AUTOTUNE_EVIDENCE.json": _check_autotune,
    "ASYNC_EVIDENCE.json": _check_async,
    "STALENESS_EVIDENCE.json": _check_staleness,
    "SHARD_EVIDENCE.json": _check_shard,
    "MEMORY_EVIDENCE.json": _check_memory,
    "FLEETSCALE_EVIDENCE.json": _check_fleetscale,
    "FEDERATE_EVIDENCE.json": _check_federate,
}


@pytest.mark.parametrize(
    "fname", sorted(EVIDENCE_CHECKS), ids=sorted(EVIDENCE_CHECKS)
)
def test_committed_evidence_schema(fname):
    """Every committed ``*_EVIDENCE.json`` artifact must exist, parse,
    and satisfy its family's schema check (the acceptance facts the
    artifact was committed to carry)."""
    path = os.path.join(REPO, fname)
    assert os.path.exists(path), f"{fname} missing"
    lines = [
        json.loads(l) for l in open(path).read().splitlines()
        if l.startswith("{")
    ]
    assert lines, f"{fname} carries no JSON lines"
    EVIDENCE_CHECKS[fname](lines)
