"""The control comes out not correct: the plain reference in the system's
place with its compressed layers in int4 (one precision below the
configured int8) and its fp32 stem in bf16, judged by the harness's own
comparison, reads above each configuration's ``logit_rel_l2_max``, here at the rehearsal's smoke size on the CPU, on the
chip at the cells' own size (``control.py``; readings in PERF.md)."""
import json

import pytest

import control
import rehearse
import run
from conftest import BENCH


@pytest.mark.parametrize("name", ["sparse-cnn-s.d3of8", "sparse-cnn-s.d1of8"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_int4_control_reads_above_the_limit(name, seed):
    import jax

    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "offline.json").read_text())
    config, traffic = rehearse.smoke(config, traffic)
    family = run.load(BENCH / "families" / "sparse_cnn.py", "fam")
    reference = run.load(BENCH / "references" / "sparse_cnn.py", "ref")
    correct, checks = control.reading(jax, config, traffic, family, reference, seed)
    assert correct is False
    assert checks["answers_missing"]["value"] == 0
    assert checks["logit_rel_l2_max"]["value"] > checks["logit_rel_l2_max"]["limit"]
