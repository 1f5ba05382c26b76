"""Frozen serving plans (DESIGN.md §10) and the tiles they pin.

Covers the ops-layer pad-to-tile path (bit-exact against the references
for fp and the int8 epilogue chain), plan semantics (bit-identical
serving, immutability, staleness detection, tiles frozen at build time),
and the tiles every tiled launch of the benchmark's three configurations
freezes at bucket 128: ``kernels/core.py``'s rule, compared with a table
of the tiles each launch ran with on the chip before this rule became
the only one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.quant import QuantDBBWeight
from repro.core.vdbb import DBBFormat, dbb_decode, dbb_encode
from repro.kernels import core, ops, ref

FMT = DBBFormat(8, 3, "matrix")


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
    def test_bit_identical_to_unplanned(self):
        model, qparams, xb = _quantized_smoke_cnn()
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4)
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))
        np.testing.assert_array_equal(  # checked apply(plan=) form
            np.asarray(model.apply(qparams, xb, plan=plan)), np.asarray(want)
        )

    def test_plan_tiles_frozen_into_closures(self, monkeypatch):
        """A plan's tiles are pinned at build time: its first trace does
        not resolve the tile rule again."""
        model, qparams, xb = _quantized_smoke_cnn()
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4)
        assert plan.tiles  # the rule's tiles, recorded per layer

        def no_rule(*a, **k):
            raise AssertionError(f"plan trace resolved the tile rule: {a}")

        for name in ("default_matmul_tiles", "default_conv_tiles",
                     "default_bf", "default_kb"):
            monkeypatch.setattr(core, name, no_rule)
        jax.clear_caches()  # the kernels' own jit caches would hide a lookup
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_ref_mode_plan_matches(self):
        model, qparams, xb = _quantized_smoke_cnn(kernel_mode="ref")
        want = model.apply(qparams, xb)
        plan = model.plan(qparams, batch=4)
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_fp_model_plan_matches(self):
        """Plans also stage the non-quantized (fp compressed) chain. The
        plan is one jitted chain, so it is held to the jitted forward:
        eager ``apply`` runs op by op, and XLA's fusion may round the fp32
        chain in the last ulp differently."""
        from repro.configs import smoke_cnn_config
        from repro.models.cnn import SparseCNN

        cfg = smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625)
        model = SparseCNN(cfg)
        params = model.compress(model.init(jax.random.PRNGKey(0)))
        xb = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3))
        want = jax.jit(model.apply)(params, xb)
        plan = model.plan(params, batch=4)
        np.testing.assert_array_equal(np.asarray(plan.serve(xb)), np.asarray(want))

    def test_stale_plan_after_requantize_raises(self):
        from repro.models.plan import StalePlanError

        model, qparams, xb = _quantized_smoke_cnn()
        plan = model.plan(qparams, batch=4)
        # re-quantize with different calibration: the plan's staged weight
        # buffers no longer match the params — serving must refuse
        params = model.compress(model.init(jax.random.PRNGKey(0)))
        _, stats2 = model.apply(params, xb * 2.0, collect_act_stats=True)
        q2 = model.quantize(params, stats2)
        with pytest.raises(StalePlanError):
            model.apply(q2, xb, plan=plan)

    def test_plan_is_immutable(self):
        model, qparams, xb = _quantized_smoke_cnn()
        plan = model.plan(qparams, batch=4)
        with pytest.raises(dataclasses.FrozenInstanceError):
            plan.fingerprint = "tampered"
        with pytest.raises(dataclasses.FrozenInstanceError):
            plan.layers[0].tiles = ()

    def test_plan_rejects_stats_collection(self):
        model, qparams, xb = _quantized_smoke_cnn()
        plan = model.plan(qparams, batch=4)
        with pytest.raises(ValueError, match="frozen hot path"):
            model.apply(qparams, xb, plan=plan, collect_act_stats=True)

    def test_linear_make_plan_honors_out_scale_fallback(self):
        """The fp/unfused fallback branch requantizes at out_scale, like
        the conv twin (the staged chain may feed an int8 consumer)."""
        from repro.core.quant import quantize as quantize_array
        from repro.core.sparse_linear import DBBLinear

        lin = DBBLinear(32, 16, fmt=DBBFormat(8, 3, "matrix"))
        params = lin.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
        out_s = jnp.float32(0.07)
        run, tiles = lin.make_plan(params, batch=8, relu=True, out_scale=out_s)
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


def test_plan_set_rejects_other_tune():
    """Tiles come from one rule: ``plan_set`` keeps ``tune`` only for the
    benchmark's ``tune="cache"`` callers and refuses anything else."""
    model, qparams, _ = _quantized_smoke_cnn()
    with pytest.raises(ValueError, match="kernels/core.py"):
        model.plan_set(qparams, buckets=(4,), tune="search")


# ---------------------------------------------------------------------------
# the tiles each benchmark cell's launches freeze at bucket 128
# ---------------------------------------------------------------------------

BUCKET = 128
CONFIGS = {  # the benchmark's configurations, by their registry entries
    "sparse-cnn-s.d3of8": ("sparse-cnn-s", 0.625),
    "sparse-cnn-s.d1of8": ("sparse-cnn-s", 0.875),
    "resnet50.d4of8": ("sparse-resnet50", 0.5),
}


def _model(config: str):
    from repro.configs import get_cnn_config
    from repro.models.cnn import SparseCNN
    from repro.models.resnet import ResNetConfig, SparseResNet

    name, sparsity = CONFIGS[config]
    cfg = dataclasses.replace(get_cnn_config(name, sparsity=sparsity),
                              kernel_mode="pallas")
    return (SparseResNet if isinstance(cfg, ResNetConfig) else SparseCNN)(cfg)


def _qweight(layer, seed: int = 0) -> QuantDBBWeight:
    """A random int8 compressed weight at the layer's real width."""
    rng = np.random.default_rng(seed)
    fmt = layer.fmt
    if hasattr(layer, "kh"):
        k, n = layer.kh * layer.kw * layer.in_channels, layer.out_channels
    else:
        k, n = layer.in_features, layer.out_features
    nb = k // fmt.bz
    pos = np.sort(np.argsort(rng.random((nb, fmt.bz)), axis=1)[:, :fmt.nnz], axis=1)
    pos = np.repeat(pos[:, :, None], n // fmt.group_size(n), axis=2)
    return QuantDBBWeight(
        jnp.asarray(rng.integers(-127, 128, (nb, fmt.nnz, n), dtype=np.int8)),
        jnp.asarray(pos.astype(np.int8)),
        jnp.asarray(rng.random(n, dtype=np.float32)), fmt, (k, n))


def _head_tiles(head):
    _, tiles = head.make_plan({"w": _qweight(head), "aq": 1.0}, batch=BUCKET,
                              fused=True)
    return tiles, {"kb": head.in_features // head.fmt.bz}


def _conv_dims(m, h, w) -> dict:
    ho, wo = m.out_hw(h, w)
    return {"bf": m.out_channels, "tile_h": ho, "tile_w": wo}


def _cnn_launch(model, launch: str):
    """``(tiles, dims)`` of ``l<i>`` of a SparseCNN on the §9 int8 chain."""
    layers = model.layers()
    i = int(launch[1:])
    if i == len(layers) - 1:
        return _head_tiles(layers[i])
    h = w = model.cfg.image_size
    for m in layers[:i]:
        h, w = m.out_hw(h, w)
    m = layers[i]
    dims = _conv_dims(m, h, w)
    if i == 0:
        # the fp32 stem runs on XLA's conv in a CPU plan (interpret-mode
        # Pallas loses to it there); on the chip the plan freezes the rule
        return core.default_conv_tiles(dims["tile_h"], dims["tile_w"], m.out_channels,
                                       m.kh * m.kw * m.in_channels, 4), dims
    _, tiles = m.make_plan({"w": _qweight(m), "aq": 1.0}, batch=BUCKET, h=h,
                           w=w, relu=True, out_scale=1.0, fused=True)
    return tiles, dims


def _resnet_launch(model, launch: str):
    """``(tiles, dims)`` of ``s<i>b<j>.<conv>`` or ``fc`` of a SparseResNet
    on the §9 int8 chain, staged through its block's ``make_plan``."""
    from repro.models.resnet import pool_hw

    if launch == "fc":
        return _head_tiles(model.head())
    block, conv = launch.split(".")
    size = model.cfg.image_size
    h, w = pool_hw(*model.stem().out_hw(size, size))
    for b in model.blocks():
        if b.name == block:
            break
        h, w = b.out_hw(h, w)
    params = {f"{b.name}.{k}": {"w": _qweight(m), "aq": 1.0, "oq": 1.0}
              for k, m in b.convs()}
    _, tiles = b.make_plan(params, batch=BUCKET, h=h, w=w, out_scale=1.0,
                           fused=True)
    m = dict(b.convs())[conv]
    dims = _conv_dims(m, *((b.out_hw(h, w)) if conv == "c3" else (h, w)))
    return {k.split(".")[1]: v for k, v in tiles.items()
            if k.split(".")[0] == conv}, dims


# The tiles each launch freezes from kernels/core.py's rule: all of F in one
# block for every conv, 1×1 and k×k.
CELL_TILES = {
    'sparse-cnn-s.d3of8': {
        'l0': {'bf': 64, 'tile_h': 64, 'tile_w': 64},
        'l1': {'bf': 64, 'tile_h': 64, 'tile_w': 64},
        'l2': {'bf': 128, 'tile_h': 32, 'tile_w': 32},
        'l3': {'bf': 128, 'tile_h': 32, 'tile_w': 32},
        'l4': {'bf': 256, 'tile_h': 16, 'tile_w': 16},
        'l5': {'bf': 256, 'tile_h': 16, 'tile_w': 16},
        'l6': {'bf': 512, 'tile_h': 8, 'tile_w': 8},
        'l7': {'bf': 512, 'tile_h': 8, 'tile_w': 8},
        'l8': {'bm': 128, 'bn': 256, 'kb': 16},
    },
    'sparse-cnn-s.d1of8': {
        'l0': {'bf': 64, 'tile_h': 64, 'tile_w': 64},
        'l1': {'bf': 64, 'tile_h': 64, 'tile_w': 64},
        'l2': {'bf': 128, 'tile_h': 32, 'tile_w': 32},
        'l3': {'bf': 128, 'tile_h': 32, 'tile_w': 32},
        'l4': {'bf': 256, 'tile_h': 16, 'tile_w': 16},
        'l5': {'bf': 256, 'tile_h': 16, 'tile_w': 16},
        'l6': {'bf': 512, 'tile_h': 8, 'tile_w': 8},
        'l7': {'bf': 512, 'tile_h': 8, 'tile_w': 8},
        'l8': {'bm': 128, 'bn': 256, 'kb': 16},
    },
    'resnet50.d4of8': {
        's1b1.c1': {'bf': 64, 'tile_h': 56, 'tile_w': 56},
        's1b1.c2': {'bf': 64, 'tile_h': 56, 'tile_w': 56},
        's1b1.c3': {'bf': 256, 'tile_h': 56, 'tile_w': 56},
        's1b1.proj': {'bf': 256, 'tile_h': 56, 'tile_w': 56},
        's1b2.c1': {'bf': 64, 'tile_h': 56, 'tile_w': 56},
        's2b1.c1': {'bf': 128, 'tile_h': 56, 'tile_w': 56},
        's2b1.c2': {'bf': 128, 'tile_h': 28, 'tile_w': 28},
        's2b1.c3': {'bf': 512, 'tile_h': 28, 'tile_w': 28},
        's2b1.proj': {'bf': 512, 'tile_h': 28, 'tile_w': 28},
        's2b2.c1': {'bf': 128, 'tile_h': 28, 'tile_w': 28},
        's2b2.c2': {'bf': 128, 'tile_h': 28, 'tile_w': 28},
        's3b1.c1': {'bf': 256, 'tile_h': 28, 'tile_w': 28},
        's3b1.c2': {'bf': 256, 'tile_h': 14, 'tile_w': 14},
        's3b1.c3': {'bf': 1024, 'tile_h': 14, 'tile_w': 14},
        's3b1.proj': {'bf': 1024, 'tile_h': 14, 'tile_w': 14},
        's3b2.c1': {'bf': 256, 'tile_h': 14, 'tile_w': 14},
        's3b2.c2': {'bf': 256, 'tile_h': 14, 'tile_w': 14},
        's4b1.c1': {'bf': 512, 'tile_h': 14, 'tile_w': 14},
        's4b1.c2': {'bf': 512, 'tile_h': 7, 'tile_w': 7},
        's4b1.c3': {'bf': 2048, 'tile_h': 7, 'tile_w': 7},
        's4b1.proj': {'bf': 2048, 'tile_h': 7, 'tile_w': 7},
        's4b2.c1': {'bf': 512, 'tile_h': 7, 'tile_w': 7},
        's4b2.c2': {'bf': 512, 'tile_h': 7, 'tile_w': 7},
        'fc': {'bm': 128, 'bn': 256, 'kb': 16},
    },
}


@pytest.mark.parametrize("config,launch", [
    (config, launch) for config, launches in CELL_TILES.items()
    for launch in launches])
def test_cell_tiles_match_parent(config, launch):
    """The tiles a launch's ``make_plan`` freezes are the table's, and each
    conv tile divides its dimension (each K tile its block count)."""
    model = _model(config)
    fn = _cnn_launch if config.startswith("sparse-cnn") else _resnet_launch
    tiles, dims = fn(model, launch)
    assert dict(tiles) == CELL_TILES[config][launch]
    for name, dim in dims.items():
        assert dim % tiles[name] == 0, (name, tiles[name], dim)


# (F, weight rows, bytes an entry, the F block): all of F while the
# double-buffered weight block fits core.CONV_WEIGHT_VMEM, else the largest
# lane-aligned divisor that does
@pytest.mark.parametrize("f,k,itemsize,bf", [
    (512, 2304, 1, 512),     # ResNet-50 s4's 3×3 at 4/8: 1.2 MB
    (2048, 256, 1, 2048),    # its last c3 (1×1, 512 → 2048)
    (64, 27, 4, 64),         # the fp32 stem
    (2048, 4608, 4, 128),    # a dense fp32 3×3, 512 → 2048: 38 MB whole
    (1024, 4608, 1, 512),    # int8, 512 → 1024: 9.4 MB whole, 4.7 MB at 512
    (192, 10**5, 1, 192),    # no lane-aligned divisor: the whole dim
])
def test_default_bf_whole_f_within_vmem(f, k, itemsize, bf):
    assert core.default_bf(f, k, itemsize) == bf
    assert bf == f or 2 * k * itemsize * bf <= core.CONV_WEIGHT_VMEM
