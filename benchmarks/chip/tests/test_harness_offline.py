"""A whole run of each offline cell at a tiny size on the CPU: sound,
under each fault the cell can have, and with the control in the
program's place.  The look for a chip is skipped; everything else is the
run."""

import pytest

import control
from tiny import answer_altered, half_batch_mean, tiny_cell, tiny_run

CELLS = ["b0.offline.r224.b256", "v3l.offline.r224.b256"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = tiny_run(tiny_cell(name))
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_err"]["value"] < 1e-4
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [answer_altered, half_batch_mean],
                         ids=["answer_altered", "half_batch_mean"])
def test_fault_is_not_correct(name, fault):
    res = tiny_run(tiny_cell(name), wrap=fault)
    assert res["correct"] is False
    assert res["checks"]["logit_err"]["value"] > 1e-2


@pytest.mark.parametrize("name", CELLS)
def test_control_wrap_is_not_correct(name):
    cell = tiny_cell(name)
    res = tiny_run(cell, wrap=control.control_wrap(cell.cfg))
    assert res["correct"] is False
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]


def test_memory_in_window_counts_the_programs_temporaries():
    import jax
    import jax.numpy as jnp

    import run
    x = jnp.ones((2048,), jnp.float32)
    step = jax.jit(lambda v: jnp.sin(v[:, None] * v[None, :]).sum(0)
                   ).lower(x).compile()
    held = run.memory_in_window(step, inflight=2)
    # the (2048, 2048) float32 intermediate lives only inside the program
    assert held >= 2048 * 2048 * 4 + x.nbytes + 2 * 2048 * 4
    w = run.Window(memory_bytes=held)
    assert run.memory_peak(jax.devices()[:1], w) >= held
