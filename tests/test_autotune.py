"""Closed-loop autotuner suite (`runtime.autotune` + the config pin
layer): policy determinism and hysteresis, the never-fight-a-pin rule,
env coverage for the previously env-less knobs, apply-side
observability, and the off-by-default background loop."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu.runtime import autotune as at
from tensorframes_tpu.runtime import profiler
from tensorframes_tpu.runtime.profiler import PROFILE_SCHEMA
from tensorframes_tpu.utils import telemetry


def _hist(buckets, counts, hsum, count):
    return {"buckets": list(buckets), "counts": list(counts),
            "sum": float(hsum), "count": int(count)}


def _fill_profile(mean_fill, samples=30, rungs=(4096,)):
    """Minimal profile whose bucketing section reports one fill
    histogram with the given mean."""
    return {
        "schema": PROFILE_SCHEMA,
        "bucketing": {
            "fill": {
                "map_blocks": _hist(
                    [0.5, 1.0], [samples, 0, 0],
                    mean_fill * samples, samples,
                )
            }
        },
        "programs": {"abc": {"rungs": list(rungs), "execs": samples}},
    }


def _ingest_profile(comp_busy, comp_wait, dec_busy, dec_wait, chunks=20):
    return {
        "schema": PROFILE_SCHEMA,
        "ingest": {
            "compute": {"chunks": chunks, "busy_s": comp_busy,
                        "wait_s": comp_wait},
            "decode": {"chunks": chunks, "busy_s": dec_busy,
                       "wait_s": dec_wait},
        },
    }


# ---------------------------------------------------------------------------
# config pin layer
# ---------------------------------------------------------------------------


class TestConfigPins:
    def test_update_pins(self):
        with config.override(shape_bucket_growth=1.9):
            assert config.is_explicit("shape_bucket_growth")
        assert not config.is_explicit("shape_bucket_growth")

    def test_set_tuned_refused_on_pin(self):
        with config.override(shape_bucket_growth=1.9):
            assert not config.set_tuned("shape_bucket_growth", 1.2)
            assert config.get().shape_bucket_growth == 1.9
            assert "shape_bucket_growth" not in config.tuned()

    def test_set_tuned_applies_and_resets(self):
        assert config.set_tuned("stream_prefetch_depth", 3)
        assert config.get().stream_prefetch_depth == 3
        assert config.tuned() == {"stream_prefetch_depth": 3}
        config.reset_tuning()
        assert config.get().stream_prefetch_depth == config.default_value(
            "stream_prefetch_depth"
        )
        assert config.tuned() == {}

    def test_update_supersedes_tuned(self):
        config.set_tuned("stream_prefetch_depth", 3)
        config.update(stream_prefetch_depth=5)
        try:
            assert config.tuned() == {}
            assert config.is_explicit("stream_prefetch_depth")
            # a later tuning attempt loses to the pin
            assert not config.set_tuned("stream_prefetch_depth", 2)
            assert config.get().stream_prefetch_depth == 5
        finally:
            # update() pins process-wide; undo for test isolation
            config._EXPLICIT.discard("stream_prefetch_depth")
            config.update(
                stream_prefetch_depth=config.default_value(
                    "stream_prefetch_depth"
                )
            )
            config._EXPLICIT.discard("stream_prefetch_depth")

    def test_override_restores_tuned_value(self):
        config.set_tuned("stream_prefetch_depth", 3)
        with config.override(stream_prefetch_depth=7):
            assert config.get().stream_prefetch_depth == 7
            assert config.is_explicit("stream_prefetch_depth")
        assert config.get().stream_prefetch_depth == 3
        assert not config.is_explicit("stream_prefetch_depth")
        assert config.tuned() == {"stream_prefetch_depth": 3}

    def test_unknown_key_raises(self):
        with pytest.raises(AttributeError):
            config.set_tuned("no_such_knob", 1)
        with pytest.raises(AttributeError):
            config.default_value("no_such_knob")


class TestEnvCoverage:
    """The satellite: serve_queue_limit / serve_default_timeout_s /
    admission_queue_limit gain TFS_* env overrides with the
    malformed-env-falls-back-to-default convention, and a well-formed
    env seed counts as an explicit pin."""

    def _probe(self, env):
        code = (
            "from tensorframes_tpu import config\n"
            "c = config.get()\n"
            "import json\n"
            "print(json.dumps({\n"
            "  'serve_queue_limit': c.serve_queue_limit,\n"
            "  'serve_default_timeout_s': c.serve_default_timeout_s,\n"
            "  'admission_queue_limit': c.admission_queue_limit,\n"
            "  'autotune': c.autotune,\n"
            "  'autotune_interval_s': c.autotune_interval_s,\n"
            "  'explicit': sorted(config.explicit_keys()),\n"
            "}))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_env_overrides_seed_and_pin(self):
        got = self._probe({
            "TFS_SERVE_QUEUE_LIMIT": "7",
            "TFS_SERVE_DEFAULT_TIMEOUT_S": "2.5",
            "TFS_ADMISSION_QUEUE_LIMIT": "9",
            "TFS_AUTOTUNE_INTERVAL_S": "5",
        })
        assert got["serve_queue_limit"] == 7
        assert got["serve_default_timeout_s"] == 2.5
        assert got["admission_queue_limit"] == 9
        assert got["autotune_interval_s"] == 5.0
        for key in ("serve_queue_limit", "serve_default_timeout_s",
                    "admission_queue_limit", "autotune_interval_s"):
            assert key in got["explicit"]

    def test_malformed_env_falls_back_unpinned(self):
        got = self._probe({
            "TFS_SERVE_QUEUE_LIMIT": "not-a-number",
            "TFS_SERVE_DEFAULT_TIMEOUT_S": "??",
            "TFS_ADMISSION_QUEUE_LIMIT": "",
        })
        assert got["serve_queue_limit"] == 256
        assert got["serve_default_timeout_s"] == 30.0
        assert got["admission_queue_limit"] == 32
        for key in ("serve_queue_limit", "serve_default_timeout_s",
                    "admission_queue_limit"):
            assert key not in got["explicit"]

    def test_autotune_env(self):
        got = self._probe({"TFS_AUTOTUNE": "1"})
        assert got["autotune"] is True
        assert "autotune" in got["explicit"]
        got = self._probe({})
        assert got["autotune"] is False


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------


class TestLadderPolicy:
    def test_shrinks_on_low_fill(self):
        recs = at.ladder_policy(_fill_profile(0.55), growth=2.0,
                                min_bucket=8)
        growth = [r for r in recs if r.knob == "shape_bucket_growth"]
        assert growth and growth[0].proposed == 1.5

    def test_dead_band_no_move(self):
        # fill between FILL_LOW and FILL_HIGH: a borderline signal
        # recommends nothing — the hysteresis band
        recs = at.ladder_policy(_fill_profile(0.85), growth=1.5,
                                min_bucket=8)
        assert not [r for r in recs if r.knob == "shape_bucket_growth"]

    def test_widens_on_recompile_storm(self):
        prof = _fill_profile(0.97, rungs=list(range(8, 8 + 20)))
        recs = at.ladder_policy(prof, growth=1.1, min_bucket=8,
                                recompile_warn_shapes=16)
        growth = [r for r in recs if r.knob == "shape_bucket_growth"]
        assert growth and growth[0].proposed > 1.1

    def test_low_fill_with_storm_does_not_shrink(self):
        # both signals bad -> shrinking would trade one storm for a
        # worse one; the policy stays put
        prof = _fill_profile(0.55, rungs=list(range(8, 8 + 20)))
        recs = at.ladder_policy(prof, growth=2.0, min_bucket=8,
                                recompile_warn_shapes=16)
        assert not [r for r in recs if r.knob == "shape_bucket_growth"]

    def test_insufficient_samples(self):
        recs = at.ladder_policy(
            _fill_profile(0.4, samples=at.MIN_FILL_SAMPLES - 1),
            growth=2.0, min_bucket=8,
        )
        assert not [r for r in recs if r.knob == "shape_bucket_growth"]

    def test_serving_fill_never_drives_the_ladder(self):
        """serve:* fill is a batching-window signal (the batcher pads
        to the rung itself): it must not trigger a ladder re-shape
        that would invalidate every warm-compiled endpoint."""
        prof = _fill_profile(0.55)
        prof["bucketing"]["fill"] = {
            "serve:ep": prof["bucketing"]["fill"]["map_blocks"]
        }
        recs = at.ladder_policy(prof, growth=2.0, min_bucket=8)
        assert not [r for r in recs if r.knob == "shape_bucket_growth"]

    def test_min_raise_step_bounded(self):
        recs = at.ladder_policy(_fill_profile(0.85, rungs=[4096]),
                                growth=1.5, min_bucket=8)
        mins = [r for r in recs if r.knob == "shape_bucket_min"]
        assert mins and mins[0].proposed == 8 * at.MIN_RAISE_STEP

    def test_min_hysteresis_band(self):
        # smallest rung under MIN_RAISE_FACTOR x min: no raise
        recs = at.ladder_policy(
            _fill_profile(0.85, rungs=[8 * at.MIN_RAISE_FACTOR - 1]),
            growth=1.5, min_bucket=8,
        )
        assert not [r for r in recs if r.knob == "shape_bucket_min"]

    def test_growth_step_bound_halves_excess(self):
        recs = at.ladder_policy(_fill_profile(0.30), growth=3.0,
                                min_bucket=8)
        growth = [r for r in recs if r.knob == "shape_bucket_growth"]
        assert growth and growth[0].proposed == 2.0  # 1 + (3-1)/2


class TestIngestPolicy:
    def test_starved_decode_bound_adds_worker_and_depth(self):
        recs = at.ingest_policy(
            _ingest_profile(1.0, 2.0, 2.6, 0.4),
            decode_workers=2, prefetch_depth=1, max_workers=8,
        )
        knobs = {r.knob: r.proposed for r in recs}
        assert knobs.get("ingest_decode_workers") == 3
        assert knobs.get("stream_prefetch_depth") == 3

    def test_bursty_deepens_queue_only(self):
        recs = at.ingest_policy(
            _ingest_profile(1.0, 1.0, 0.3, 2.7),
            decode_workers=2, prefetch_depth=1, max_workers=8,
        )
        knobs = {r.knob: r.proposed for r in recs}
        assert "ingest_decode_workers" not in knobs
        assert knobs.get("stream_prefetch_depth") == 2

    def test_idle_decoders_shed_worker(self):
        recs = at.ingest_policy(
            _ingest_profile(3.0, 0.05, 0.2, 2.8),
            decode_workers=3, prefetch_depth=2, max_workers=8,
        )
        knobs = {r.knob: r.proposed for r in recs}
        assert knobs.get("ingest_decode_workers") == 2

    def test_dead_band(self):
        # starved 15% (between STARVED_LOW and STARVED_HIGH): no move
        recs = at.ingest_policy(
            _ingest_profile(2.55, 0.45, 2.0, 1.0),
            decode_workers=2, prefetch_depth=1, max_workers=8,
        )
        assert recs == []

    def test_depth_at_bound_never_reports_noop_applied(self):
        # depth already at its safety ceiling: the keep-depth>=workers
        # rule must not emit a no-op recommendation every cycle
        hi = at.SAFETY_BOUNDS["stream_prefetch_depth"][1]
        recs = at.ingest_policy(
            _ingest_profile(1.0, 2.0, 2.6, 0.4),
            decode_workers=hi, prefetch_depth=hi, max_workers=hi + 4,
        )
        assert not [
            r for r in recs if r.knob == "stream_prefetch_depth"
        ]

    def test_worker_ceiling(self):
        recs = at.ingest_policy(
            _ingest_profile(1.0, 2.0, 2.6, 0.4),
            decode_workers=4, prefetch_depth=4, max_workers=4,
        )
        assert not [
            r for r in recs if r.knob == "ingest_decode_workers"
        ]

    def test_insufficient_chunks(self):
        recs = at.ingest_policy(
            _ingest_profile(1.0, 2.0, 2.6, 0.4,
                            chunks=at.MIN_INGEST_CHUNKS - 1),
            decode_workers=1, prefetch_depth=1, max_workers=8,
        )
        assert recs == []


class TestServingPolicy:
    def _profile(self, shed=0, p99_bucket=0.001, coalesce_per_batch=4,
                 requests=64, batches=16):
        counts = [batches, 0, 0] if p99_bucket <= 0.001 else [0, batches, 0]
        return {
            "schema": PROFILE_SCHEMA,
            "serving": {
                "endpoints": {
                    "ep": {"requests": requests, "batches": batches,
                           "shed": shed}
                },
                "batch_requests": _hist(
                    [1, 4, 16], [0, batches, 0, 0],
                    coalesce_per_batch * batches, batches,
                ),
                "queue_seconds": _hist(
                    [0.001, 1.0], counts, p99_bucket * batches, batches
                ),
            },
        }

    def test_shrinks_on_shed(self):
        recs = at.serving_policy(self._profile(shed=2), window_ms=5.0,
                                 default_timeout_s=30.0)
        assert recs and recs[0].scope == "endpoint:ep"
        assert recs[0].proposed == 2.5

    def test_shrinks_on_queue_pressure(self):
        recs = at.serving_policy(
            self._profile(p99_bucket=1.0), window_ms=5.0,
            default_timeout_s=1.0,
        )
        assert recs and recs[0].proposed < 5.0

    def test_widens_with_headroom_and_coalescing(self):
        recs = at.serving_policy(self._profile(), window_ms=5.0,
                                 default_timeout_s=30.0)
        assert recs and recs[0].proposed == 7.5

    def test_dead_band_no_coalescing(self):
        recs = at.serving_policy(
            self._profile(coalesce_per_batch=1.0), window_ms=5.0,
            default_timeout_s=30.0,
        )
        assert recs == []

    def test_insufficient_requests(self):
        recs = at.serving_policy(
            self._profile(requests=at.MIN_SERVE_REQUESTS - 1),
            window_ms=5.0, default_timeout_s=30.0,
        )
        assert recs == []

    def test_global_p99_pressure_gated_on_single_endpoint(self):
        """The queue histogram is process-global: with TWO batching
        endpoints, one's pressure must not shrink the other — only an
        endpoint's own shed counts."""
        prof = self._profile(p99_bucket=1.0)
        prof["serving"]["endpoints"]["other"] = {
            "requests": 64, "batches": 16, "shed": 0,
        }
        recs = at.serving_policy(prof, window_ms=5.0,
                                 default_timeout_s=1.0)
        assert recs == []  # neither shrinks on the shared p99
        prof["serving"]["endpoints"]["ep"]["shed"] = 2
        recs = at.serving_policy(prof, window_ms=5.0,
                                 default_timeout_s=1.0)
        assert [r.scope for r in recs] == ["endpoint:ep"]
        assert recs[0].proposed < 5.0

    def test_endpoint_window_override_is_current(self):
        recs = at.serving_policy(
            self._profile(), window_ms=5.0, default_timeout_s=30.0,
            endpoint_windows={"ep": 20.0},
        )
        assert recs and recs[0].current == 20.0
        assert recs[0].proposed == 30.0


class TestAdmissionPolicy:
    def test_raise_on_shed_without_saturation(self):
        prof = {
            "schema": PROFILE_SCHEMA,
            "admission": {"admitted": 64, "shed": 3, "peak_in_flight": 2},
            "residuals": {"peak_ratio_max": None},
        }
        recs = at.admission_policy(prof, limit=2)
        assert recs and recs[0].proposed == 4

    def test_cap_at_peak_under_saturation(self):
        prof = {
            "schema": PROFILE_SCHEMA,
            "admission": {"admitted": 64, "shed": 0, "peak_in_flight": 3},
            "residuals": {"peak_ratio_max": 0.9},
        }
        recs = at.admission_policy(prof, limit=0)
        assert recs and recs[0].proposed == 3

    def test_saturation_dead_band(self):
        prof = {
            "schema": PROFILE_SCHEMA,
            "admission": {"admitted": 64, "shed": 1, "peak_in_flight": 3},
            "residuals": {"peak_ratio_max": 0.4},  # between SAT_LOW/HIGH
        }
        assert at.admission_policy(prof, limit=2) == []

    def test_insufficient_evidence(self):
        prof = {
            "schema": PROFILE_SCHEMA,
            "admission": {
                "admitted": at.MIN_ADMITTED - 1, "shed": 5,
                "peak_in_flight": 2,
            },
            "residuals": {"peak_ratio_max": None},
        }
        assert at.admission_policy(prof, limit=2) == []


# ---------------------------------------------------------------------------
# determinism + hysteresis
# ---------------------------------------------------------------------------


_KNOBS = {
    "shape_bucket_growth": 2.0,
    "shape_bucket_min": 8,
    "ingest_decode_workers": 1,
    "stream_prefetch_depth": 1,
    "serve_batch_window_ms": 5.0,
    "serve_default_timeout_s": 30.0,
    "max_concurrent_verbs": 0,
    "endpoint_windows": {},
}


class TestDeterminism:
    def test_same_profile_same_recommendations(self):
        prof = _fill_profile(0.55)
        a = [r.to_dict() for r in at.recommend(prof, knobs=_KNOBS)]
        b = [r.to_dict() for r in at.recommend(prof, knobs=_KNOBS)]
        assert a == b and a

    def test_saved_profile_cross_process(self, tmp_path):
        """The acceptance case: a saved WorkloadProfile loaded in a
        FRESH interpreter recommends exactly what this process does."""
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(4100, dtype=np.float32)}, num_blocks=8
        )
        _ = tfs.map_blocks((tfs.block(df, "x") * 2.0).named("y"), df)
        path = str(tmp_path / "prof.json")
        profiler.snapshot(note="determinism").save(path)

        here = [
            r.to_dict()
            for r in at.recommend(profiler.load(path), knobs=_KNOBS)
        ]
        code = (
            "import json\n"
            "from tensorframes_tpu.runtime import autotune, profiler\n"
            f"prof = profiler.load({path!r})\n"
            f"knobs = {_KNOBS!r}\n"
            "recs = [r.to_dict() for r in autotune.recommend(prof, "
            "knobs=knobs)]\n"
            "print('RECS=' + json.dumps(recs, sort_keys=True))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [
            ln for ln in proc.stdout.splitlines() if ln.startswith("RECS=")
        ][-1]
        assert json.loads(line[len("RECS="):]) == json.loads(
            json.dumps(here, sort_keys=True)
        )

    def test_recommend_is_pure_not_compounding(self):
        # re-running on the SAME snapshot proposes the same single
        # step, never a compounded one
        prof = _fill_profile(0.55)
        for _ in range(3):
            recs = at.recommend(prof, knobs=_KNOBS)
            growth = [
                r for r in recs if r.knob == "shape_bucket_growth"
            ]
            assert growth[0].proposed == 1.5


class TestHysteresis:
    def test_borderline_signal_never_flips(self):
        """A fill signal inside the dead band recommends nothing, cycle
        after cycle — the no-oscillation contract."""
        prof = _fill_profile((at.FILL_LOW + at.FILL_HIGH) / 2)
        for _ in range(5):
            recs = at.recommend(prof, knobs=_KNOBS)
            assert not [
                r for r in recs if r.knob == "shape_bucket_growth"
            ]

    def test_converges_into_dead_band(self):
        """Simulated closed loop: each cycle's fill improves as growth
        shrinks; once fill enters the band the knob stops moving and
        never leaves."""
        growth = 3.0
        moves = 0
        for _ in range(10):
            # a cluster at 55% of a growth-g rung fills ~1/g of the
            # tuned rung: fill improves as growth shrinks
            fill = min(0.98, 0.55 * (3.0 / growth) ** 0.8)
            recs = at.ladder_policy(_fill_profile(fill), growth=growth,
                                    min_bucket=8)
            g = [r for r in recs if r.knob == "shape_bucket_growth"]
            if not g:
                break
            growth = g[0].proposed
            moves += 1
        assert moves and moves < 6
        # and the rest state is stable
        fill = min(0.98, 0.55 * (3.0 / growth) ** 0.8)
        assert not [
            r for r in at.ladder_policy(
                _fill_profile(fill), growth=growth, min_bucket=8
            )
            if r.knob == "shape_bucket_growth"
        ]

    def test_profile_delta_subtracts_history(self):
        old = _fill_profile(0.30, samples=100)
        new = _fill_profile(0.30, samples=100)
        # 100 new samples at fill ~0.95 land on top of the old 0.30s
        new["bucketing"]["fill"]["map_blocks"] = _hist(
            [0.5, 1.0], [100, 100, 0], 0.30 * 100 + 0.95 * 100, 200
        )
        delta = at.profile_delta(new, old)
        h = delta["bucketing"]["fill"]["map_blocks"]
        assert h["count"] == 100
        assert abs(h["sum"] / h["count"] - 0.95) < 1e-9
        # the cumulative view (mean 0.625) would keep shrinking; the
        # delta view (mean 0.95) rests in the band
        assert not [
            r for r in at.ladder_policy(delta, growth=1.2, min_bucket=8)
            if r.knob == "shape_bucket_growth"
        ]


# ---------------------------------------------------------------------------
# apply: pins, bounds, observability
# ---------------------------------------------------------------------------


class TestApply:
    def test_pin_survives_tuning_cycle(self):
        """THE regression from the satellite list: an explicit
        shape_bucket_growth pin survives a tuning cycle that wants to
        move it."""
        with config.override(shape_bucket_growth=2.0):
            res = tfs.autotune(_fill_profile(0.55))
            dec = [
                d for d in res["applied"]
                if d["knob"] == "shape_bucket_growth"
            ]
            assert dec and dec[0]["outcome"] == "skipped:pinned"
            assert config.get().shape_bucket_growth == 2.0
            assert "shape_bucket_growth" not in config.tuned()

    def test_applied_value_and_counter_and_span(self):
        res = tfs.autotune(_fill_profile(0.55))
        dec = [
            d for d in res["applied"]
            if d["knob"] == "shape_bucket_growth"
        ]
        assert dec and dec[0]["outcome"] == "applied"
        assert config.get().shape_bucket_growth == 1.5
        assert config.tuned()["shape_bucket_growth"] == 1.5
        flat = telemetry.flat_counters()
        assert flat.get(
            "autotune_adjustments{knob=shape_bucket_growth}"
        ) == 1.0
        spans = [s for s in telemetry.spans() if s.kind == "tuning"]
        assert any(
            s.name == "autotune.shape_bucket_growth"
            and s.attrs["outcome"] == "applied"
            for s in spans
        )
        # skipped decisions record a span too (with their outcome)
        with config.override(shape_bucket_min=8):
            tfs.autotune(_fill_profile(0.55))
        spans = [s for s in telemetry.spans() if s.kind == "tuning"]
        assert any(
            s.attrs.get("outcome") == "skipped:pinned" for s in spans
        )

    def test_safety_clamp(self):
        recs = [at.Recommendation(
            "stream_prefetch_depth", "config", 1, 99, "test"
        )]
        dec = at.apply(recs)
        lo, hi = at.SAFETY_BOUNDS["stream_prefetch_depth"]
        assert dec[0]["applied_value"] == hi
        assert config.get().stream_prefetch_depth == hi

    def test_endpoint_window_apply(self):
        from tensorframes_tpu.serving import registry

        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(64, dtype=np.float32)}
        )
        with config.override(serve_warm_compile=False):
            tfs.serving.register(
                "at-ep", (tfs.block(df, "x") * 2.0).named("y"),
                schema={"x": np.float32},
            )
        recs = [at.Recommendation(
            "serve_batch_window_ms", "endpoint:at-ep", 5.0, 7.5, "test"
        )]
        dec = at.apply(recs)
        assert dec[0]["outcome"] == "applied"
        assert registry.get("at-ep").batch_window_ms == 7.5
        assert at.state()["endpoint_windows"] == {"at-ep": 7.5}
        # unknown endpoint: a decision, not an exception
        dec = at.apply([at.Recommendation(
            "serve_batch_window_ms", "endpoint:ghost", 5.0, 7.5, "test"
        )])
        assert dec[0]["outcome"] == "skipped:unknown-endpoint"
        # a global window pin covers the per-endpoint knob
        with config.override(serve_batch_window_ms=5.0):
            dec = at.apply([at.Recommendation(
                "serve_batch_window_ms", "endpoint:at-ep", 7.5, 11.0,
                "test",
            )])
            assert dec[0]["outcome"] == "skipped:pinned"
            assert registry.get("at-ep").batch_window_ms == 7.5

    def test_ladder_change_rewarms_endpoints(self):
        """An applied ladder move re-warms every previously warmed
        endpoint — the PR 10 zero-steady-state-compiles invariant must
        survive a ladder re-shape."""
        from tensorframes_tpu.serving import registry

        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(16, dtype=np.float32)}
        )
        with config.override(serve_max_batch_rows=32):
            tfs.serving.register(
                "at-warm", (tfs.block(df, "x") * 2.0).named("y"),
                schema={"x": np.float32},
            )
        old_rungs = registry.get("at-warm").warmed_rungs
        assert old_rungs  # warm compile ran at register
        dec = at.apply([at.Recommendation(
            "shape_bucket_growth", "config", 2.0, 1.5, "test"
        )])
        assert dec[0]["outcome"] == "applied"
        new_rungs = registry.get("at-warm").warmed_rungs
        from tensorframes_tpu import shape_policy as sp

        assert new_rungs == tuple(sp.bucket_ladder(32))
        assert new_rungs != old_rungs

    def test_batcher_reads_endpoint_window(self):
        import importlib

        # serving/__init__ re-exports batcher() the function over the
        # submodule name; fetch the module itself
        batcher_mod = importlib.import_module(
            "tensorframes_tpu.serving.batcher"
        )
        from tensorframes_tpu.serving import registry

        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(64, dtype=np.float32)}
        )
        with config.override(serve_warm_compile=False):
            tfs.serving.register(
                "at-win", (tfs.block(df, "x") * 2.0).named("y"),
                schema={"x": np.float32},
            )
        ep = registry.get("at-win")
        cfg = config.get()
        assert batcher_mod._window_s(ep, cfg) == pytest.approx(
            cfg.serve_batch_window_ms / 1e3
        )
        ep.batch_window_ms = 12.0
        assert batcher_mod._window_s(ep, cfg) == pytest.approx(0.012)
        assert ep.describe()["batch_window_ms"] == 12.0
        # a LATER operator pin of the global knob overrides already-
        # tuned endpoint windows at read time — pins win, always
        with config.override(serve_batch_window_ms=3.0):
            assert batcher_mod._window_s(
                ep, config.get()
            ) == pytest.approx(0.003)
        assert batcher_mod._window_s(ep, cfg) == pytest.approx(0.012)
        # and autotune.reset() (the operator's undo + the conftest
        # hook) clears tuned endpoint windows entirely
        at.reset()
        assert ep.batch_window_ms is None


# ---------------------------------------------------------------------------
# one-shot + background loop + surfacing
# ---------------------------------------------------------------------------


class TestOneShotAndLoop:
    def test_autotune_from_saved_path(self, tmp_path):
        path = str(tmp_path / "p.json")
        profiler.WorkloadProfile(_fill_profile(0.55)).save(path)
        res = tfs.autotune(path)
        assert any(
            d["knob"] == "shape_bucket_growth"
            and d["outcome"] == "applied"
            for d in res["applied"]
        )

    def test_autotune_recommend_only(self):
        res = tfs.autotune(
            _fill_profile(0.55), apply_recommendations=False
        )
        assert res["recommendations"] and not res["applied"]
        assert "shape_bucket_growth" not in config.tuned()

    def test_off_by_default_no_thread(self):
        assert not config.get().autotune
        assert at.maybe_start() is None
        assert not any(
            t.name == "tfs-autotune" for t in threading.enumerate()
        )

    def test_stop_joins_outside_module_lock(self):
        """stop() must not hold the module lock across the join: the
        tuner thread's own cycle() -> snapshot() -> state() takes that
        lock, so the old hold-and-join always timed out mid-cycle."""
        import time

        tuner = at.AutoTuner()

        def worker():
            time.sleep(0.1)  # let stop() reach its join first
            with at._tuner_lock:  # the state() path inside a cycle
                pass

        t = threading.Thread(target=worker, name="tfs-autotune")
        tuner._thread = t
        with at._tuner_lock:
            at._tuner = tuner
        t.start()
        at.stop()
        assert not t.is_alive()

    def test_loop_starts_and_stops(self):
        with config.override(autotune=True, autotune_interval_s=30.0):
            tuner = at.maybe_start()
            assert tuner is not None and tuner.running
            assert any(
                t.name == "tfs-autotune" for t in threading.enumerate()
            )
            st = at.state()
            assert st["enabled"] and st["running"]
            at.stop()
            assert not any(
                t.name == "tfs-autotune" for t in threading.enumerate()
            )

    def test_cycle_tunes_on_deltas(self):
        """Two manual cycles: the first sees the low-fill history and
        moves the knob; the second cycle's DELTA is quiet (no new
        dispatches), so the knob rests — no compounding."""
        tuner = at.AutoTuner()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(33_000, dtype=np.float32)}, num_blocks=1
        )
        for _ in range(20):
            tfs.map_blocks((tfs.block(df, "x") * 2.0).named("y"), df)
        tuner.cycle()
        assert config.tuned().get("shape_bucket_growth") == 1.5
        tuner.cycle()  # nothing new happened: the delta has no evidence
        assert config.tuned().get("shape_bucket_growth") == 1.5

    def test_closed_loop_on_clustered_blocks_keeps_results(self):
        """Block sizes clustered just above a growth-2 rung: run,
        snapshot, recommend, apply until quiet. The loop shrinks the
        growth, the ladder's mean fill recovers, and the tuned ladder's
        map and min are bit-identical to the static one's, its sum
        within the reassociation tolerance."""
        from tensorframes_tpu import shape_policy as sp
        from tensorframes_tpu.runtime import costmodel
        from tensorframes_tpu.runtime.executor import Executor

        sizes = [33_000 + (i * 37) % 4_000 for i in range(16)]
        static_growth = config.default_value("shape_bucket_growth")
        static_fill = float(np.mean([
            s / sp.bucket_for(s, growth=static_growth, min_bucket=8)
            for s in sizes
        ]))
        assert static_fill <= 0.70  # the workload wastes >= 30% as set
        n = sum(sizes)
        col = tfs.TensorFrame.from_dict(
            {"x": (np.arange(n) % 251).astype(np.float32)}
        )["x"]
        df = tfs.TensorFrame([col], list(np.cumsum([0] + sizes)))

        def workload(ex):
            x = tfs.block(df, "x")
            y = (dsl.tanh(x * 0.5) * 2.0 + x).named("y")
            mn = dsl.reduce_min(
                tfs.block(df, "x", tf_name="x_input"), axes=[0]
            ).named("x")
            s = dsl.reduce_sum(
                tfs.block(df, "x", tf_name="x_input"), axes=[0]
            ).named("x")
            return {
                "map": np.asarray(
                    tfs.map_blocks(y, df, executor=ex)["y"].values
                ),
                "min": float(np.asarray(
                    tfs.reduce_blocks(mn, df, executor=ex)
                )),
                "sum": float(np.asarray(
                    tfs.reduce_blocks(s, df, executor=ex)
                )),
            }

        with config.override(block_scheduler="off"):
            ref = workload(Executor())
            probe = Executor()
            moves = []
            for _ in range(4):
                telemetry.reset()
                costmodel.reset()
                workload(probe)
                moved = [
                    d for d in tfs.autotune()["applied"]
                    if d["outcome"] == "applied"
                ]
                moves += moved
                if not moved:
                    break
            tuned_growth = config.get().shape_bucket_growth
            tuned_fill = float(np.mean(
                [s / sp.bucket_for(s) for s in sizes]
            ))
            got = workload(Executor())
        assert [d for d in moves if d["knob"] == "shape_bucket_growth"]
        assert tuned_growth < static_growth
        assert tuned_fill > static_fill + 0.1, (static_fill, tuned_fill)
        np.testing.assert_array_equal(got["map"], ref["map"])
        assert got["min"] == ref["min"]
        np.testing.assert_allclose(got["sum"], ref["sum"], rtol=1e-5)

    def test_diagnostics_and_profile_surface_state(self):
        config.set_tuned("stream_prefetch_depth", 3)
        data = tfs.diagnostics(format="json")
        assert data["autotune"]["tuned"] == {"stream_prefetch_depth": 3}
        text = tfs.diagnostics()
        assert "tuned stream_prefetch_depth = 3" in text
        prof = profiler.snapshot()
        assert prof.data["autotune"]["tuned"] == {
            "stream_prefetch_depth": 3
        }
        assert "peak_ratio_max" in prof.data["residuals"]
