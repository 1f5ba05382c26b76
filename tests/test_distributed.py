"""Distributed-correctness tests on a small multi-device host mesh.

These run in a SUBPROCESS with --xla_force_host_platform_device_count=8 so
the main test process keeps its single-device view (per the dry-run spec,
the device-count override must never leak into other tests).

Checks, numerically (not just compile):
  - sharded train_step == single-device train_step (DP+TP equivalence)
  - sharded decode_step == single-device decode_step
  - the dry-run harness itself succeeds end-to-end on a small mesh
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

# Multi-device subprocess checks: each test compiles a sharded program in a
# fresh 8-device interpreter — the slowest tier-1 block (see pyproject slow
# marker). CI runs `-m "not slow"`; the full tier-1 suite still runs these.
pytestmark = pytest.mark.slow

REPO = pathlib.Path(__file__).resolve().parents[1]


def run_sub(code: str) -> dict:
    """Run python code with 8 fake host devices; return parsed last line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=540,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


COMMON = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import make_batch, smoke_config
from repro.models.common import sharding_rules
from repro.models.model import LM
from repro.optim.adamw import OptConfig, init_state
from repro.sharding.rules import make_rules
from repro.train.step import make_serve_step, make_train_step
assert len(jax.devices()) == 8
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
"""


@pytest.mark.parametrize("arch", ["codeqwen1.5-7b", "internvl2-2b"])
def test_sharded_train_step_matches_single_device(arch):
    code = COMMON + textwrap.dedent(f"""
    cfg = smoke_config("{arch}")
    import dataclasses
    cfg = dataclasses.replace(cfg, d_model=64, d_ff=128, vocab_size=512, num_layers=2)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params, OptConfig())
    batch = make_batch(cfg, batch=4, seq=32)
    fn = make_train_step(model, OptConfig())
    # single device reference
    p_ref, _, m_ref = jax.jit(fn)(params, opt, batch, jnp.int32(0))
    # sharded
    rules = make_rules(cfg, tp=4, mode="train")
    pspecs = model.pspecs(rules)
    psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
    osh = {{"m": psh, "v": psh, "count": NamedSharding(mesh, P())}}
    if "master" in opt:
        osh["master"] = psh
    bsh = {{k: NamedSharding(mesh, P(("data",), *([None]*(v.ndim-1)))) for k, v in batch.items()}}
    with mesh, sharding_rules(rules):
        p_sh, _, m_sh = jax.jit(fn, in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())))(
            params, opt, batch, jnp.int32(0))
    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
             for a, b in zip(jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p_sh))]
    print(json.dumps({{"loss_ref": float(m_ref["loss"]), "loss_sh": float(m_sh["loss"]),
                       "max_param_diff": max(diffs)}}))
    """)
    r = run_sub(code)
    assert abs(r["loss_ref"] - r["loss_sh"]) < 5e-3, r
    assert r["max_param_diff"] < 5e-3, r


def test_sharded_decode_matches_single_device():
    code = COMMON + textwrap.dedent("""
    cfg = smoke_config("qwen2-72b")
    model = LM(cfg)
    params = model.constrain(model.init(jax.random.PRNGKey(0)))
    served = model.compress(params)
    cache = model.init_cache(batch_size=4, max_len=32)
    batch = make_batch(cfg, batch=4, seq=1, kind="serve")
    fn = make_serve_step(model)
    lg_ref, _ = jax.jit(fn)(served, cache, batch, jnp.int32(7))
    rules = make_rules(cfg, tp=4, mode="decode")
    with mesh, sharding_rules(rules):
        lg_sh, _ = jax.jit(fn)(served, cache, batch, jnp.int32(7))
    d = float(jnp.max(jnp.abs(lg_ref.astype(jnp.float32) - lg_sh.astype(jnp.float32))))
    print(json.dumps({"max_logit_diff": d}))
    """)
    r = run_sub(code)
    assert r["max_logit_diff"] < 5e-2, r  # bf16 reduction-order noise


def test_dryrun_harness_small_mesh():
    """The dry-run lowering path works end-to-end (tiny config, 2x4 mesh)."""
    code = COMMON + textwrap.dedent("""
    import dataclasses
    from repro.launch import dryrun as dr
    cfg = smoke_config("qwen2.5-32b")
    rules = make_rules(cfg, tp=4, mode="train")
    compiled = dr._lower(cfg, "train_4k", mesh, rules, seq_len=64, global_batch=4)
    cost = dr.cost_analysis_dict(compiled)
    coll = dr.collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({"flops": cost.get("flops", 0),
                      "coll": coll["total_bytes"],
                      "temp": getattr(mem, "temp_size_in_bytes", 0)}))
    """)
    r = run_sub(code)
    assert r["flops"] > 0
    assert r["coll"] > 0  # TP on a 4-way model axis must emit collectives
