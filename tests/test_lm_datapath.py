"""LM VDBB datapath: compressed/quantized routing, plans, parity (§13).

The PR-8 contract: an LM forward over DBB-compressed params must execute
the *compressed* matmul formulation — ``dbb_decode`` never runs on the
hot path (asserted with a decode spy, mirroring the jnp.pad spy in
test_fused_epilogue.py) — and a frozen ``LM.plan()`` must serve
bit-identical to the jitted unplanned forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.quant import QuantDBBWeight
from repro.core.vdbb import DBBFormat, DBBWeight, dbb_encode
from repro.models import common
from repro.models.model import LM


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def tiny():
    """qwen2-tiny: params, constrained + compressed + calibrated forms."""
    cfg = get_config("qwen2-tiny")
    model = LM(cfg)
    params = model.constrain(model.init(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    cparams = model.compress(params)
    _, stats = model.forward(batch=batch, params=cparams,
                             collect_act_stats=True)
    qparams = model.quantize(cparams, stats)
    return dict(cfg=cfg, model=model, params=params, cparams=cparams,
                qparams=qparams, stats=stats, tokens=tokens, batch=batch)


# ---------------------------------------------------------------------------
# the bugfix: no dense materialization on the compressed hot path
# ---------------------------------------------------------------------------


class TestNoDenseFallback:
    def test_compressed_forward_never_decodes(self, tiny, monkeypatch):
        """A compressed ('matrix'-group) LM forward must route every
        projection through the gather formulation — the pre-PR-8
        ``x @ dbb_decode(w)`` fallback is a silent densification."""
        calls = []
        real = common.dbb_decode
        monkeypatch.setattr(
            common, "dbb_decode",
            lambda *a, **k: (calls.append(1), real(*a, **k))[1])
        logits = tiny["model"].forward(tiny["cparams"], tiny["batch"])
        assert logits.shape[-1] == tiny["cfg"].padded_vocab
        assert not calls, "compressed forward materialized a dense weight"

    def test_quantized_forward_never_decodes(self, tiny, monkeypatch):
        calls = []
        real = common.dbb_decode
        monkeypatch.setattr(
            common, "dbb_decode",
            lambda *a, **k: (calls.append(1), real(*a, **k))[1])
        tiny["model"].forward(tiny["qparams"], tiny["batch"])
        assert not calls

    def test_bw_weight_decodes(self, monkeypatch):
        """Positive control: a per-column ('bw') pattern has no shared
        gather layout, so apply_linear documents dbb_decode as its only
        ref formulation — the spy must fire there."""
        calls = []
        real = common.dbb_decode
        monkeypatch.setattr(
            common, "dbb_decode",
            lambda *a, **k: (calls.append(1), real(*a, **k))[1])
        w = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
        dw = dbb_encode(w, DBBFormat(8, 3, None), prune=True)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
        common.apply_linear(x, dw)
        assert calls


# ---------------------------------------------------------------------------
# forward parity: compressed / quantized / ref-vs-pallas
# ---------------------------------------------------------------------------


class TestForwardParity:
    def test_compressed_matches_dense(self, tiny):
        """The gather formulation contracts nnz-per-block instead of the
        zero-padded K — same MACs in a different order, so fp32 parity is
        tight but not bitwise."""
        dense = tiny["model"].forward(tiny["params"], tiny["batch"])
        comp = tiny["model"].forward(tiny["cparams"], tiny["batch"])
        assert _rel(comp, dense) < 1e-5

    def test_quantized_within_5pct(self, tiny):
        """Same end-to-end INT8 accuracy gate as the CNN (test_quant)."""
        dense = tiny["model"].forward(tiny["params"], tiny["batch"])
        q = tiny["model"].forward(tiny["qparams"], tiny["batch"])
        assert _rel(q, dense) < 0.05

    def test_pallas_matches_ref(self, tiny):
        pcfg = dataclasses.replace(tiny["cfg"], kernel_mode="pallas")
        pmodel = LM(pcfg)
        ref_c = tiny["model"].forward(tiny["cparams"], tiny["batch"])
        pal_c = pmodel.forward(tiny["cparams"], tiny["batch"])
        assert _rel(pal_c, ref_c) < 1e-5
        # quantized: both formulations sum the same int32 products
        ref_q = tiny["model"].forward(tiny["qparams"], tiny["batch"])
        pal_q = pmodel.forward(tiny["qparams"], tiny["batch"])
        np.testing.assert_array_equal(np.asarray(pal_q), np.asarray(ref_q))


# ---------------------------------------------------------------------------
# quantize lifecycle
# ---------------------------------------------------------------------------


class TestQuantizeLifecycle:
    def test_leaves_quantized_with_act_scales(self, tiny):
        """Every compressed projection leaf becomes QuantDBBWeight and the
        calibration attaches an ``<leaf>_aq`` sibling (stacked leaves get
        one scale per layer group)."""
        lp = tiny["qparams"]["layers"]["b0"]
        for name in ("wq", "wk", "wv", "wo"):
            assert isinstance(lp["mixer"][name], QuantDBBWeight)
            aq = lp["mixer"][f"{name}_aq"]
            assert aq.shape == (tiny["cfg"].num_groups,)
        for name in ("w_up", "w_gate", "w_down"):
            assert isinstance(lp["mlp"][name], QuantDBBWeight)
            assert lp["mlp"][f"{name}_aq"].shape == (tiny["cfg"].num_groups,)
        # embeddings and lm_head are not DBB-tagged: they stay dense fp
        assert isinstance(tiny["qparams"]["lm_head"], jnp.ndarray)
        assert "lm_head_aq" not in tiny["qparams"]

    def test_quantize_without_stats_is_dynamic(self, tiny):
        """No calibration → no ``_aq`` siblings; forward still works
        (dynamic per-call act scales)."""
        qp = tiny["model"].quantize(tiny["cparams"])
        assert "lm_head_aq" not in qp
        assert "wq_aq" not in qp["layers"]["b0"]["mixer"]
        dense = tiny["model"].forward(tiny["params"], tiny["batch"])
        q = tiny["model"].forward(qp, tiny["batch"])
        assert _rel(q, dense) < 0.05

    def test_act_stat_names_are_scoped(self, tiny):
        names = {s.name for s in tiny["stats"]}
        assert "g0.b0.mixer.wq" in names
        assert "g0.b0.mlp.w_down" in names
        assert "lm_head" in names


# ---------------------------------------------------------------------------
# frozen LM plans
# ---------------------------------------------------------------------------


class TestLMPlan:
    def test_plan_bit_identical_to_forward(self, tiny):
        model, tokens = tiny["model"], tiny["tokens"]
        f = jax.jit(lambda p, t: model.forward(p, {"tokens": t}))
        for params in (tiny["cparams"], tiny["qparams"]):
            plan = model.plan(params, batch=2, seq=16)
            np.testing.assert_array_equal(
                np.asarray(plan(tokens)), np.asarray(f(params, tokens)))

    def test_plan_stages(self, tiny):
        plan = tiny["model"].plan(tiny["cparams"], batch=2, seq=16)
        names = [lp.name for lp in plan.layers]
        assert names[0] == "embed" and names[-1] == "head"
        assert "g0.b0" in names and "g1.b0" in names

    def test_stale_plan_raises(self, tiny):
        from repro.models.plan import StalePlanError

        plan = tiny["model"].plan(tiny["cparams"], batch=2, seq=16)
        plan.check(tiny["cparams"])  # same params: fine
        with pytest.raises(StalePlanError):
            plan.check(tiny["qparams"])

    def test_unsupported_configs_raise(self, tiny):
        xcfg = dataclasses.replace(tiny["cfg"], cross_attn=True)
        with pytest.raises(NotImplementedError):
            LM(xcfg).plan(tiny["cparams"], batch=2, seq=16)


# ---------------------------------------------------------------------------
# satellite bugfixes
# ---------------------------------------------------------------------------


class TestRaggedKPlan:
    def test_make_plan_rejects_ragged_k(self):
        """in_features not a multiple of bz used to silently floor-divide
        into a wrong frozen kb; it must be a clear error."""
        from repro.core.sparse_linear import DBBLinear

        fmt = DBBFormat(8, 3, "matrix")
        lin = DBBLinear(24, 32, fmt, kernel_mode="pallas")
        dw = lin.compress_params(lin.init(jax.random.PRNGKey(0)))
        ragged = dataclasses.replace(lin, in_features=20)
        with pytest.raises(ValueError, match="not a multiple"):
            ragged.make_plan(dw, batch=16)
        run, tiles = lin.make_plan(dw, batch=16)  # exact K: fine
        assert tiles

    def test_ref_mode_unaffected(self):
        from repro.core.sparse_linear import DBBLinear

        fmt = DBBFormat(8, 3, "matrix")
        lin = DBBLinear(24, 32, fmt, kernel_mode="ref")
        dw = lin.compress_params(lin.init(jax.random.PRNGKey(0)))
        ragged = dataclasses.replace(lin, in_features=20)
        run, tiles = ragged.make_plan(dw, batch=16)
        assert tiles == {}  # ref mode never freezes pallas tiles


class TestMoEAuxLoss:
    def test_uniform_router_pins_one(self):
        """The importance loss ``E · Σ frac²`` is minimized at exactly 1.0
        by a uniform router (the docstring used to claim it was an entropy
        regularizer)."""
        from repro.models.mlp import MoEMLP

        cfg = dataclasses.replace(
            get_config("qwen2-tiny"), num_experts=8, top_k=2)
        moe = MoEMLP(cfg)
        p = {"router": jnp.zeros((cfg.d_model, cfg.num_experts))}
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.d_model))
        np.testing.assert_allclose(
            float(moe.aux_loss(p, x)), 1.0, rtol=1e-6)

    def test_concentrated_router_exceeds_one(self):
        from repro.models.mlp import MoEMLP

        cfg = dataclasses.replace(
            get_config("qwen2-tiny"), num_experts=8, top_k=2)
        moe = MoEMLP(cfg)
        w = jnp.zeros((cfg.d_model, cfg.num_experts)).at[:, 0].set(50.0)
        x = jnp.ones((2, 16, cfg.d_model))
        assert float(moe.aux_loss({"router": w}, x)) > 4.0
