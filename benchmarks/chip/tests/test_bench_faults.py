"""A whole run (skipping only the look for a chip, at the rehearsal's smoke
size, on the CPU) decides ``correct`` true when the system is sound and
false when the timed path is broken underneath it: for a served model, an
answer altered where it is produced."""
import argparse
import json

import jax.numpy as jnp
import pytest

import rehearse
import run

CELLS = [w["name"] for w in run.read_json(run.ROOT / "BENCHMARK.json")["workloads"]]


def result(capsys, cell):
    args = argparse.Namespace(workload=cell, seed=2**31 + 99, seconds=1.0, trace=0)
    assert run.measure(args, find_devices=rehearse.cpu_devices, resize=rehearse.smoke) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    out = result(capsys, cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(capsys, monkeypatch, cell):
    from repro.models import plan

    serve = plan.ModelPlan.serve
    # every answer's logits shifted by one class where the plan produces them
    monkeypatch.setattr(plan.ModelPlan, "serve",
                        lambda self, x: jnp.roll(serve(self, x), 1, axis=-1))
    out = result(capsys, cell)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["logit_rel_l2_max"]["value"] > checks["logit_rel_l2_max"]["limit"]
