"""``correct`` comes out false when the timed path is broken underneath,
and when the bfloat16 control stands in the program's place. Faults are
planted in the program before the run builds it; the harness's look for a
chip is skipped and the rest of the run is driven at the tiny size. A cell
on one chip has no exchange between chips to leave out."""
import jax
import jax.numpy as jnp
import pytest

from bench import compare, data, harness, reference
from bench.tests import tiny
from repro.core import fedsim
from repro.models import cnn


def _unchanged_state(mp):
    build = fedsim.FederatedSimulation._sgd_one_fn

    def broken(self):
        sgd_one = build(self)

        def keep(p, dx, dy, idx):            # computes, returns p unchanged
            return p, sgd_one(p, dx, dy, idx)[1]

        return keep

    mp.setattr(fedsim.FederatedSimulation, "_sgd_one_fn", broken)


def _altered_loss(mp):
    build = fedsim.FederatedSimulation._sgd_one_fn

    def broken(self):
        sgd_one = build(self)

        def misreport(p, dx, dy, idx):      # trains, reports a wrong loss
            out, loss = sgd_one(p, dx, dy, idx)
            return out, 1.1 * loss

        return misreport

    mp.setattr(fedsim.FederatedSimulation, "_sgd_one_fn", broken)


def _half_batch(mp):
    loss = cnn.loss
    mp.setattr(cnn, "loss", lambda p, x, y: loss(p, x[:x.shape[0] // 2],
                                                 y[:y.shape[0] // 2]))


def _altered_pi(mp):
    em = fedsim.em_refine_loop

    def broken(*a, **k):
        comps, pi, hist = em(*a, **k)
        return comps, jnp.roll(pi, 1), hist

    mp.setattr(fedsim, "em_refine_loop", broken)


def _altered_eval(mp):
    acc = cnn.masked_accuracy
    mp.setattr(cnn, "masked_accuracy",
               lambda p, x, y, m: acc(p, x, (y + 1) % 3, m))


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _altered_loss, _altered_pi,
                                   _altered_eval],
                         ids=["unchanged", "half_batch", "altered_loss",
                              "altered_pi", "altered_eval"])
def test_a_broken_program_is_not_correct(tmp_path, monkeypatch, plant):
    plant(monkeypatch)
    cat = tiny.make_bench(str(tmp_path))
    res = harness.run_cell(tiny.CELL, 5, 0.1, False, catalog=cat,
                           require_chip=False, log=lambda s: None)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def _reference(dtype=jnp.float32, fault=None):
    fed = data.make_federation(tiny.CONFIG, 7)
    seed = data.derived_seed(7)
    return reference.Reference(tiny.CONFIG, "pfedwn", fed, seed, dtype=dtype,
                               fault=fault).run(2)


def test_the_bfloat16_control_is_not_correct():
    nums = compare.numbers(_reference(jnp.bfloat16), _reference())
    assert not compare.passes(nums, tiny.LIMITS)


@pytest.mark.parametrize("fault", reference.faults("pfedwn"))
def test_faults_planted_in_the_reference_are_caught(fault):
    nums = compare.numbers(_reference(fault=fault), _reference())
    assert not compare.passes(nums, tiny.LIMITS)


def test_the_altered_pi_reaches_the_mix():
    base = _reference()
    nums = compare.numbers(_reference(fault="altered_pi"), base)
    assert nums["pi_gap.r1"] > 0
    assert nums["target_loss_gap.r1"] > 0     # the mix took the rolled π
    assert nums["loss_gap.r1"] == nums["target_loss_gap.r1"]


def test_the_reference_draws_the_programs_weights_and_minibatches():
    fed = data.make_federation(tiny.CONFIG, 3)
    sim = harness.build_sim(tiny.CONFIG, {"method": "pfedwn",
                                          "rounds_per_call": 1,
                                          "eval_every": 1}, fed, 99,
                            recorder=None)
    ref = reference.Reference(tiny.CONFIG, "pfedwn", fed, 99)
    keys = jax.random.split(jax.random.PRNGKey(99), 4)
    ref_params = jax.vmap(lambda k: ref.model.init_params(
        k, tiny.CONFIG["model_config"]))(keys)
    for a, b in zip(jax.tree.leaves(sim.params0), jax.tree.leaves(ref_params)):
        assert jnp.array_equal(a, b)
