"""Tile autotuner, persistent cache, pad-to-tile, and frozen serving plans
(DESIGN.md §10).

Covers the §10 contracts: deterministic cache keys, cache round-trip
(write → reload → no re-search), version-mismatch invalidation, the
ops-layer pad-to-tile path (bit-exact vs the references for fp and the
int8 epilogue chain), registry-driven default tiles, and plan semantics
(bit-identical serving, immutability, staleness detection).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.vdbb import DBBFormat, dbb_decode, dbb_encode
from repro.kernels import autotune, core, ops, ref

FMT = DBBFormat(8, 3, "matrix")


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test sees (and leaves) an empty tuned-tile registry."""
    core.clear_tuned()
    yield
    core.clear_tuned()


# ---------------------------------------------------------------------------
# cache keys + persistence
# ---------------------------------------------------------------------------


class TestCacheKeys:
    def test_deterministic(self):
        sig = core.matmul_sig(64, 128, 96, 8, 3, jnp.float32)
        a = autotune.cache_key(core.KIND_MATMUL_TC, sig, backend="cpu")
        b = autotune.cache_key(core.KIND_MATMUL_TC, sig, backend="cpu")
        assert a == b

    def test_distinguishes_everything(self):
        base = autotune.cache_key(
            core.KIND_MATMUL_TC, core.matmul_sig(64, 128, 96, 8, 3, jnp.float32),
            backend="cpu",
        )
        variants = [
            autotune.cache_key(  # kernel kind
                core.KIND_MATMUL_BW,
                core.matmul_sig(64, 128, 96, 8, 3, jnp.float32), backend="cpu"),
            autotune.cache_key(  # shape
                core.KIND_MATMUL_TC,
                core.matmul_sig(65, 128, 96, 8, 3, jnp.float32), backend="cpu"),
            autotune.cache_key(  # nnz
                core.KIND_MATMUL_TC,
                core.matmul_sig(64, 128, 96, 8, 4, jnp.float32), backend="cpu"),
            autotune.cache_key(  # dtype
                core.KIND_MATMUL_TC,
                core.matmul_sig(64, 128, 96, 8, 3, jnp.int8), backend="cpu"),
            autotune.cache_key(  # backend
                core.KIND_MATMUL_TC,
                core.matmul_sig(64, 128, 96, 8, 3, jnp.float32), backend="tpu"),
        ]
        assert len({base, *variants}) == len(variants) + 1

    def test_conv_sig_includes_geometry(self):
        a = core.conv_sig(2, 16, 16, 32, 64, 3, 3, 1, 1, 8, 3, jnp.float32)
        b = core.conv_sig(2, 8, 8, 32, 64, 3, 3, 2, 2, 8, 3, jnp.float32)
        assert a != b


class TestTuneCache:
    def test_round_trip_no_research(self, tmp_path, monkeypatch):
        path = tmp_path / "cache.json"
        res = autotune.tune_matmul(
            64, 128, 96, FMT, top_k=2, reps=1, cache=autotune.TuneCache(path)
        )
        assert res.source == "search" and path.exists()

        # a reloaded cache must answer without searching at all
        def boom(*a, **k):
            raise AssertionError("search ran despite a cache hit")

        monkeypatch.setattr(autotune, "_search", boom)
        replay = autotune.tune_matmul(
            64, 128, 96, FMT, top_k=2, reps=1, cache=autotune.TuneCache(path)
        )
        assert replay.source == "cache"
        assert replay.tiles == res.tiles
        assert replay.measured_us == res.measured_us

    def test_version_mismatch_invalidates(self, tmp_path):
        path = tmp_path / "cache.json"
        cache = autotune.TuneCache(path)
        cache.put("k", {"tiles": {"bm": 8}})
        cache.save()
        data = json.loads(path.read_text())
        data["version"] = autotune.CACHE_VERSION + 1
        path.write_text(json.dumps(data))
        assert autotune.TuneCache(path).get("k") is None

    def test_corrupt_file_is_empty_cache(self, tmp_path):
        path = tmp_path / "cache.json"
        path.write_text("{not json")
        assert autotune.TuneCache(path).entries == {}

    def test_search_installs_registry(self, tmp_path):
        res = autotune.tune_matmul(
            64, 128, 96, FMT, top_k=2, reps=1,
            cache=autotune.TuneCache(tmp_path / "c.json"),
        )
        sig = core.matmul_sig(64, 128, 96, 8, 3, jnp.float32)
        assert core.lookup_tiles(core.KIND_MATMUL_TC, sig) == res.tiles

    def test_default_always_measured(self, tmp_path):
        """The pick_tile baseline is in every search's candidate set, so
        measured-best ≤ measured-default and modeled-best ≤ modeled-default
        hold by construction."""
        res = autotune.tune_matmul(
            64, 128, 96, FMT, top_k=1, reps=1,
            cache=autotune.TuneCache(tmp_path / "c.json"),
        )
        assert res.measured_us <= res.default_us
        assert res.modeled_best_us <= res.modeled_default_us


# ---------------------------------------------------------------------------
# pad-to-tile (the pick_tile-pathology fix)
# ---------------------------------------------------------------------------


class TestPadToTile:
    def test_pick_tile_padded(self):
        assert core.pick_tile_padded(200, 128, 4) == (100, 200)  # good divisor
        # no multiple of 8 in [64, 128] divides 200: pad to an aligned tile
        assert core.pick_tile_padded(200, 128, 8) == (128, 256)
        assert core.pick_tile_padded(96, 128, 8) == (96, 96)     # whole dim
        # 2·prime beyond 2x the default: pad instead of one huge tile
        assert core.pick_tile_padded(514, 128, 8) == (128, 640)
        # the 1000-class head: N pads to 1024, never bn=250 (no lane multiple)
        assert core.pick_tile_padded(1000, 256, 128) == (256, 1024)

    def test_pad_tile_explicit(self):
        assert core.pad_tile(130, 64, 128) == (64, 192)  # non-divisor pads
        assert core.pad_tile(130, 130, 128) == (130, 130)
        assert core.pad_tile(100, 128, 128) == (100, 100)  # clamped, no pad
        assert core.pad_tile(200, None, 128) == (100, 200)  # None → pick path

    @pytest.mark.parametrize("m,k,n", [(127, 64, 96), (130, 128, 150), (64, 64, 257)])
    @pytest.mark.parametrize("group", ["matrix", None, 4])
    def test_fp_bit_exact_vs_unpadded(self, m, k, n, group):
        """Padded launches return exactly what the reference computes —
        zero rows/columns contribute nothing."""
        if group == 4 and n % 4:
            n -= n % 4
        fmt = DBBFormat(8, 3, group)
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        a = jax.random.normal(k1, (m, k))
        dw = dbb_encode(jax.random.normal(k2, (k, n)), fmt, prune=True)
        got = ops.vdbb_matmul(a, dw, bm=64, bn=64, kb=2, interpret=True)
        assert got.shape == (m, n)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.dbb_matmul_ref(a, dw)),
            rtol=1e-4, atol=1e-4,
        )

    def test_quant_epilogue_padded_bit_exact(self):
        """int8 datapath + full fused epilogue through the pad path matches
        the integer oracle bit-for-bit."""
        m, k, n = 100, 64, 72
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        a = jax.random.normal(k1, (m, k))
        qw = quant.quantize_dbb(
            dbb_encode(jax.random.normal(k2, (k, n)), FMT, prune=True)
        )
        b = jax.random.normal(k3, (n,))
        s_a = quant.dynamic_act_scale(a)
        out_s = jnp.float32(0.05)
        got = ops.quant_matmul(a, qw, s_a, bias=b, relu=True, out_scale=out_s,
                               bm=64, bn=64, kb=4, interpret=True)
        acc = quant.int_matmul_ref(quant.quantize(a, s_a), dbb_decode(qw.as_dbb()))
        want = ref.quant_epilogue_ref(acc, s_a * qw.scales, bias=b, relu=True,
                                      out_scale=out_s)
        assert got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_registry_defaults_flow_through_ops(self):
        """An installed tuned config changes the default-tile launch and
        stays bit-close to the reference."""
        m, k, n = 64, 128, 96
        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        a = jax.random.normal(k1, (m, k))
        dw = dbb_encode(jax.random.normal(k2, (k, n)), FMT, prune=True)
        want = ref.dbb_matmul_ref(a, dw)
        sig = core.matmul_sig(m, k, n, 8, 3, jnp.float32)
        autotune.install(core.KIND_MATMUL_TC, sig, {"bm": 32, "bn": 48, "kb": 4})
        got = ops.vdbb_matmul(a, dw, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        # non-dividing tuned tiles take the pad path instead of raising
        autotune.install(core.KIND_MATMUL_TC, sig, {"bm": 60, "bn": 50, "kb": 3})
        got = ops.vdbb_matmul(a, dw, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_registry_change_invalidates_live_traces(self, monkeypatch):
        """Default-tile traces capture the registry lookup at trace time;
        ``set_tuned``/``clear_tuned`` must force a retrace so the new
        config is actually consulted — an unchanged re-install must not."""
        calls = []
        orig = core.lookup_tiles
        monkeypatch.setattr(core, "lookup_tiles",
                            lambda *a: calls.append(a) or orig(*a))
        m, k, n = 32, 64, 48
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        a = jax.random.normal(k1, (m, k))
        dw = dbb_encode(jax.random.normal(k2, (k, n)), FMT, prune=True)
        ops.vdbb_matmul(a, dw, interpret=True)   # traces, consults registry
        n_trace = len(calls)
        assert n_trace > 0
        ops.vdbb_matmul(a, dw, interpret=True)   # cached: no new lookup
        assert len(calls) == n_trace
        sig = core.matmul_sig(m, k, n, 8, 3, jnp.float32)
        core.set_tuned(core.KIND_MATMUL_TC, sig, {"bm": 16, "bn": 16, "kb": 2})
        ops.vdbb_matmul(a, dw, interpret=True)   # invalidated: re-consults
        assert len(calls) > n_trace
        n_trace = len(calls)
        # identical re-install is a no-op: live traces stay valid
        core.set_tuned(core.KIND_MATMUL_TC, sig, {"bm": 16, "bn": 16, "kb": 2})
        ops.vdbb_matmul(a, dw, interpret=True)
        assert len(calls) == n_trace


# ---------------------------------------------------------------------------
# conv tuning
# ---------------------------------------------------------------------------


class TestTuneConv:
    def test_search_and_replay(self, tmp_path):
        cache = autotune.TuneCache(tmp_path / "c.json")
        res = autotune.tune_conv(1, 8, 8, 16, 32, 3, 3, FMT, top_k=1, reps=1,
                                 cache=cache)
        assert res.source == "search"
        assert res.measured_us <= res.default_us
        replay = autotune.tune_conv(1, 8, 8, 16, 32, 3, 3, FMT, top_k=1, reps=1,
                                    cache=autotune.TuneCache(cache.path))
        assert replay.source == "cache" and replay.tiles == res.tiles

    def test_tuned_conv_tiles_guard_divisibility(self):
        sig = core.conv_sig(1, 8, 8, 16, 32, 3, 3, 1, 1, 8, 3, jnp.float32)
        core.set_tuned(core.KIND_CONV_TC, sig, {"bf": 5, "tile_h": 4, "tile_w": 3})
        bf, th, tw = core.tuned_conv_tiles(core.KIND_CONV_TC, sig, 8, 8, 32)
        assert (bf, th, tw) == (None, 4, None)  # only dividing components used


# ---------------------------------------------------------------------------
# frozen serving plans
# ---------------------------------------------------------------------------


def _quantized_smoke_cnn(kernel_mode="pallas"):
    from repro.configs import smoke_cnn_config
    from repro.models.cnn import SparseCNN

    cfg = dataclasses.replace(
        smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625),
        kernel_mode=kernel_mode,
    )
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    xb = jax.random.normal(
        jax.random.PRNGKey(1), (4, cfg.image_size, cfg.image_size, cfg.in_channels)
    )
    _, stats = model.apply(params, xb, collect_act_stats=True)
    return model, model.quantize(params, stats), xb


class TestModelPlan:
    def test_bit_identical_to_unplanned(self, tmp_path):
        model, qparams, xb = _quantized_smoke_cnn()
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4, tune="off")
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))
        np.testing.assert_array_equal(  # checked apply(plan=) form
            np.asarray(model.apply(qparams, xb, plan=plan)), np.asarray(want)
        )

    def test_bit_identical_with_searched_tiles(self, tmp_path):
        model, qparams, xb = _quantized_smoke_cnn()
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4, tune="search",
                          cache=tmp_path / "c.json", top_k=1, reps=1)
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_plan_tiles_frozen_into_closures(self, tmp_path, monkeypatch):
        """A plan's tile configs are pinned at build time — its first trace
        must not consult the ambient registry (which may have been cleared
        or re-tuned by another model since the plan was built)."""
        model, qparams, xb = _quantized_smoke_cnn()
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4, tune="search",
                          cache=tmp_path / "c.json", top_k=1, reps=1)
        assert plan.tiles  # searched configs recorded
        core.clear_tuned()  # ambient state changes before the first trace

        def no_lookup(*a):
            raise AssertionError(f"plan trace consulted the registry: {a}")

        monkeypatch.setattr(core, "lookup_tiles", no_lookup)
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_ref_mode_plan_matches(self):
        model, qparams, xb = _quantized_smoke_cnn(kernel_mode="ref")
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4, tune="off")
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_fp_model_plan_matches(self):
        """Plans also stage the non-quantized (fp compressed) chain."""
        from repro.configs import smoke_cnn_config
        from repro.models.cnn import SparseCNN

        cfg = smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625)
        model = SparseCNN(cfg)
        params = model.compress(model.init(jax.random.PRNGKey(0)))
        xb = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3))
        want = model.apply(params, xb)
        plan = model.plan(params, batch=4, tune="off")
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_stale_plan_after_requantize_raises(self):
        from repro.models.plan import StalePlanError

        model, qparams, xb = _quantized_smoke_cnn()
        plan = model.plan(qparams, batch=4, tune="off")
        # re-quantize with different calibration: the plan's staged weight
        # buffers no longer match the params — serving must refuse
        params = model.compress(model.init(jax.random.PRNGKey(0)))
        _, stats2 = model.apply(params, xb * 2.0, collect_act_stats=True)
        q2 = model.quantize(params, stats2)
        with pytest.raises(StalePlanError):
            model.apply(q2, xb, plan=plan)

    def test_plan_is_immutable(self):
        model, qparams, xb = _quantized_smoke_cnn()
        plan = model.plan(qparams, batch=4, tune="off")
        with pytest.raises(dataclasses.FrozenInstanceError):
            plan.fingerprint = "tampered"
        with pytest.raises(dataclasses.FrozenInstanceError):
            plan.layers[0].tiles = ()

    def test_plan_rejects_stats_collection(self):
        model, qparams, xb = _quantized_smoke_cnn()
        plan = model.plan(qparams, batch=4, tune="off")
        with pytest.raises(ValueError, match="frozen hot path"):
            model.apply(qparams, xb, plan=plan, collect_act_stats=True)

    def test_linear_make_plan_honors_out_scale_fallback(self):
        """The fp/unfused fallback branch requantizes at out_scale, like
        the conv twin (the staged chain may feed an int8 consumer)."""
        from repro.core.quant import quantize as quantize_array
        from repro.core.sparse_linear import DBBLinear
        from repro.core.vdbb import DBBFormat

        lin = DBBLinear(32, 16, fmt=DBBFormat(8, 3, "matrix"))
        params = lin.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
        out_s = jnp.float32(0.07)
        run, tiles = lin.make_plan(params, batch=8, relu=True, out_scale=out_s,
                                   tune="off")
        got = run(x)
        want = quantize_array(jax.nn.relu(lin(params, x)), out_s)
        assert got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fingerprint_tracks_content(self):
        from repro.models.plan import params_fingerprint

        model, qparams, xb = _quantized_smoke_cnn()
        f1 = params_fingerprint(qparams)
        assert f1 == params_fingerprint(qparams)  # deterministic
        bumped = dict(qparams)
        bumped["l0"] = dict(qparams["l0"], b=qparams["l0"]["b"] + 1.0)
        assert params_fingerprint(bumped) != f1
