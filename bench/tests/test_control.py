"""The control, the reference computed in bfloat16 in the program's place,
comes out not correct through the run's own comparison; the same code in
float32 comes out correct.  A small cut of the configuration, on the
CPU; the chip runs of the control at the cell's own size are in
PERF.md."""
import jax.numpy as jnp
import pytest

import control
import harness

CUT = {"authors": 3200, "pubs": 6000}


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_bfloat16_control_is_not_correct(seed):
    cfg, module = harness.load_config("dblp-q1")
    cfg.update(CUT)
    mix = harness.load_mix("ppr-uniform.dblp-q1")
    low = control.control_run(cfg, module, mix, seed, 10.0, 0.85, 20, jnp.bfloat16)
    f32 = control.control_run(cfg, module, mix, seed, 10.0, 0.85, 20, jnp.float32)
    assert low["answers"] > 0
    assert low["correct"] is False
    assert low["checks"]["ppr_gap"]["value"] > 3 * low["checks"]["ppr_gap"]["limit"]
    assert low["checks"]["unanswered"]["value"] == 0
    assert f32["correct"] is True
