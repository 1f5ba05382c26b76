"""Substrate tests: optimizer, data pipeline, checkpointing, fault
tolerance (kill/resume equivalence), elastic reshard-on-load, gradient
compression, DBB training integration (loss decreases under constraint).
"""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.configs import smoke_config
from repro.core.sparse_linear import PruneSchedule
from repro.core.vdbb import satisfies_dbb
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticTokens
from repro.models.model import LM
from repro.optim.adamw import OptConfig, apply_updates, init_state, schedule
from repro.train.loop import LoopConfig, Trainer
from repro.train.step import make_train_step


def small_model(name="codeqwen1.5-7b", **over):
    cfg = smoke_config(name)
    cfg = dataclasses.replace(
        cfg, num_layers=2, d_model=64, d_ff=128, vocab_size=256, **over
    )
    return LM(cfg)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


class TestOptimizer:
    def test_schedule_warmup_and_decay(self):
        cfg = OptConfig(peak_lr=1.0, warmup_steps=10, decay_steps=100, min_lr_frac=0.1)
        assert float(schedule(0, cfg)) == 0.0
        assert float(schedule(10, cfg)) == pytest.approx(1.0, rel=1e-3)
        assert float(schedule(100, cfg)) == pytest.approx(0.1, rel=1e-3)

    def test_adamw_descends_quadratic(self):
        cfg = OptConfig(peak_lr=0.1, warmup_steps=0, decay_steps=100, weight_decay=0.0, clip_norm=1e9)
        params = {"w": jnp.array([3.0, -2.0])}
        st = init_state(params, cfg)
        for step in range(200):
            g = {"w": 2 * params["w"]}
            params, st, _ = apply_updates(params, g, st, step, cfg)
        assert float(jnp.abs(params["w"]).max()) < 0.05

    def test_grad_compression_error_feedback(self):
        cfg = OptConfig(peak_lr=0.05, warmup_steps=0, decay_steps=500,
                        weight_decay=0.0, clip_norm=1e9, grad_compression=True)
        params = {"w": jnp.array([3.0, -2.0, 0.5])}
        st = init_state(params, cfg)
        assert "ef" in st
        for step in range(300):
            g = {"w": 2 * params["w"]}
            params, st, _ = apply_updates(params, g, st, step, cfg)
        # int8+EF still converges on the quadratic
        assert float(jnp.abs(params["w"]).max()) < 0.1


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


class TestData:
    def test_deterministic_and_host_sharded(self):
        cfg = smoke_config("codeqwen1.5-7b")
        d0 = SyntheticTokens(cfg, DataConfig(seq_len=32, global_batch=4, host_index=0, host_count=2))
        d1 = SyntheticTokens(cfg, DataConfig(seq_len=32, global_batch=4, host_index=1, host_count=2))
        b0a, b0b = d0.batch(7), d0.batch(7)
        np.testing.assert_array_equal(b0a["tokens"], b0b["tokens"])  # pure fn of step
        assert not np.array_equal(d0.batch(7)["tokens"], d1.batch(7)["tokens"])
        assert b0a["tokens"].shape == (2, 32)
        # labels are next-token shifted
        np.testing.assert_array_equal(
            d0.batch(3)["tokens"][:, 1:], d0.batch(3)["labels"][:, :-1]
        )

    def test_prefetcher_resumes_at_step(self):
        cfg = smoke_config("codeqwen1.5-7b")
        src = SyntheticTokens(cfg, DataConfig(seq_len=16, global_batch=2))
        pf = Prefetcher(src, start_step=5)
        step, batch = pf.next()
        pf.stop()
        assert step == 5
        np.testing.assert_array_equal(batch["tokens"], src.batch(5)["tokens"])


# ---------------------------------------------------------------------------
# checkpointing + fault tolerance
# ---------------------------------------------------------------------------


class TestCheckpoint:
    def test_atomic_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.bfloat16)}}
        store.save(tmp_path, 3, tree, extra={"note": "x"})
        out, manifest = store.restore(tmp_path, tree)
        assert manifest["step"] == 3
        np.testing.assert_array_equal(out["a"], tree["a"])
        assert out["b"]["c"].dtype == jnp.bfloat16

    def test_latest_and_gc(self, tmp_path):
        tree = {"a": jnp.zeros(2)}
        ck = store.AsyncCheckpointer(tmp_path, keep=2)
        for s in (1, 2, 3):
            ck.save_async(s, tree)
        ck.wait()
        assert store.list_steps(tmp_path) == [2, 3]
        assert store.latest_step(tmp_path) == 3

    def test_structure_mismatch_rejected(self, tmp_path):
        store.save(tmp_path, 0, {"a": jnp.zeros(2)})
        with pytest.raises(AssertionError):
            store.restore(tmp_path, {"a": jnp.zeros(2), "b": jnp.zeros(1)})

    @pytest.mark.parametrize("mode", ["flip", "truncate", "manifest", "missing"])
    def test_corruption_corpus_fails_typed(self, tmp_path, mode):
        """§15 integrity: every kind of on-disk damage — a flipped byte,
        a torn (truncated) write, a manifest edited without re-digesting,
        a deleted arrays file — surfaces as CorruptCheckpointError at
        restore, never silent garbage."""
        from repro.launch.faults import corrupt_checkpoint

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                "b": jnp.ones(8, jnp.bfloat16)}
        store.save(tmp_path, 1, tree)
        corrupt_checkpoint(tmp_path, mode=mode)
        with pytest.raises(store.CorruptCheckpointError):
            store.restore(tmp_path, tree)

    @pytest.mark.parametrize("mode", ["flip", "truncate", "manifest", "missing"])
    def test_fallback_walks_back_to_verifiable_step(self, tmp_path, mode):
        """``fallback=True`` recovers the newest step whose checksums
        still verify when the latest is damaged — and still fails typed
        when *every* step is damaged."""
        from repro.launch.faults import corrupt_checkpoint

        tree = {"w": jnp.arange(12, dtype=jnp.float32)}
        store.save(tmp_path, 1, jax.tree_util.tree_map(lambda a: a + 1, tree))
        store.save(tmp_path, 2, tree)
        corrupt_checkpoint(tmp_path, step=2, mode=mode)
        out, manifest = store.restore(tmp_path, tree, fallback=True)
        assert manifest["step"] == 1
        np.testing.assert_array_equal(out["w"], np.arange(12) + 1)
        corrupt_checkpoint(tmp_path, step=1, mode=mode)
        with pytest.raises(store.CorruptCheckpointError, match="no verifiable"):
            store.restore(tmp_path, tree, fallback=True)

    def test_shape_mismatch_reports_path_and_step(self, tmp_path):
        """A leaf shape mismatch at restore names the tree path and the
        checkpoint step — not just a bare index."""
        store.save(tmp_path, 5, {"enc": {"w": jnp.zeros((2, 3))}})
        with pytest.raises(ValueError, match=r"'w'.*step 5.*\(2, 3\)"):
            store.restore(tmp_path, {"enc": {"w": jnp.zeros((3, 3))}})

    @pytest.mark.slow
    def test_kill_resume_equivalence(self, tmp_path):
        """Train 6 steps straight == train 3, 'crash', resume, train 3."""
        model = small_model()
        opt = OptConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=10)
        data = DataConfig(seq_len=16, global_batch=2)

        def train(total, ckpt_dir, ckpt_every=100):
            loop = LoopConfig(total_steps=total, ckpt_dir=str(ckpt_dir),
                              ckpt_every=ckpt_every, log_every=100)
            t = Trainer(model, opt, data, loop)
            return t.run()

        pA, _, _ = train(6, tmp_path / "a", ckpt_every=100)
        # run B: 3 steps with a checkpoint at 2... use ckpt_every=2 then resume
        loopB = LoopConfig(total_steps=3, ckpt_dir=str(tmp_path / "b"), ckpt_every=2, log_every=100)
        tB = Trainer(model, opt, data, loopB)
        tB.run()
        # "crash" after step 2's checkpoint; resume to 6
        # resume path reads latest (step 2), continues at 3
        loopB2 = LoopConfig(total_steps=6, ckpt_dir=str(tmp_path / "b"), ckpt_every=100, log_every=100)
        tB2 = Trainer(model, opt, data, loopB2)
        pB, _, _ = tB2.run()
        for a, b in zip(jax.tree_util.tree_leaves(pA), jax.tree_util.tree_leaves(pB)):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       rtol=2e-4, atol=2e-5)

    def test_elastic_reshard_on_load(self, tmp_path):
        """Checkpoints store logical shapes; restore lays out on any mesh
        (here: 1-device 'mesh' vs plain arrays — shapes preserved)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.make_mesh((1,), ("data",))
        tree = {"w": jnp.arange(8.0).reshape(4, 2)}
        store.save(tmp_path, 1, tree)
        sh = {"w": NamedSharding(mesh, P("data", None))}
        out, _ = store.restore(tmp_path, tree, shardings=sh)
        assert out["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# end-to-end: DBB-constrained training descends
# ---------------------------------------------------------------------------


class TestTrainingIntegration:
    def test_loss_decreases_with_dbb_constraint(self):
        model = small_model()
        assert model.cfg.dbb is not None
        opt = OptConfig(peak_lr=3e-3, warmup_steps=5, decay_steps=60)
        data = DataConfig(seq_len=32, global_batch=4)
        loop = LoopConfig(total_steps=60, ckpt_dir=None, log_every=59)
        t = Trainer(model, opt, data, loop, PruneSchedule(0, 20))
        params, _, history = t.run()
        assert history[-1][1] < history[0][1] - 0.2, history
        # final weights satisfy the DBB bound exactly
        from repro.models.common import dbb_leaves, tree_get

        for path, pdef in dbb_leaves(model.defs()):
            w = np.asarray(tree_get(params, path)).reshape(-1, *pdef.shape[-2:])
            assert satisfies_dbb(jnp.asarray(w[0]), pdef.dbb), path

    def test_preemption_flushes_checkpoint(self, tmp_path):
        model = small_model()
        opt = OptConfig()
        data = DataConfig(seq_len=16, global_batch=2)
        loop = LoopConfig(total_steps=50, ckpt_dir=str(tmp_path), ckpt_every=1000, log_every=100)
        t = Trainer(model, opt, data, loop)
        params, opt_state, start = t.init_or_resume()
        t._preempted = True  # simulate SIGTERM delivery
        t.run(params, opt_state, 0)
        assert store.latest_step(tmp_path) is not None  # flushed before exit


# ------------------------------------------------ persistent compile cache
@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_use_compile_cache(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the fixed in-checkout directory."""
    from repro import xla_utils

    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = xla_utils.use_compile_cache()
        if env_set:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            repo = pathlib.Path(__file__).resolve().parents[1]
            assert got == str(repo / ".cache" / "jax")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
