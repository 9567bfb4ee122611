"""Runs the benchmark's rehearsal with the timed path BROKEN underneath
(tests only): ``python bm_launcher.py <fault> <run.py arguments>``.

Each fault is planted in the program, below everything the benchmark
drives, so the run's own comparison has to find it."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def frozen_state():
    """A step that returns its state unchanged."""
    from learningorchestra_tpu.runtime import engine

    real = engine.Engine._train_step_body

    def body(self, state, batch, rng):
        new_state, metrics = real(self, state, batch, rng)
        return state.replace(step=new_state.step), metrics

    engine.Engine._train_step_body = body


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    import jax
    from learningorchestra_tpu.runtime import engine

    real = engine.Engine._micro_grads

    def grads(self, params, model_state, batch, rng):
        half = jax.tree_util.tree_map(
            lambda a: a[:max(1, a.shape[0] // 2)], batch)
        return real(self, params, model_state, half, rng)

    engine.Engine._micro_grads = grads


def frozen_from_epoch1():
    """The state left unchanged from the second epoch on: what a fault
    of the steady-state executable alone looks like. The first epoch,
    which a fit runs through another executable, is sound."""
    import jax
    import jax.numpy as jnp
    from learningorchestra_tpu.runtime import engine

    real = engine.Engine._train_step_body
    first_epoch = 3  # the rehearsal's steps_per_epoch

    def body(self, state, batch, rng):
        new_state, metrics = real(self, state, batch, rng)
        keep = state.step >= first_epoch
        held = jax.tree_util.tree_map(
            lambda old, new: jnp.where(keep, old, new),
            (state.params, state.opt_state),
            (new_state.params, new_state.opt_state))
        return new_state.replace(params=held[0], opt_state=held[1]), metrics

    engine.Engine._train_step_body = body


def dies_in_window():
    """The window's job is lost after 40 epochs, inside the window."""
    from learningorchestra_tpu.runtime import preempt

    real = preempt.heartbeat
    lost = []

    def heartbeat(*args, **kwargs):
        if kwargs.get("epoch") == 40 and not lost:
            lost.append(True)
            raise RuntimeError("planted: the job is lost")
        return real(*args, **kwargs)

    preempt.heartbeat = heartbeat


if __name__ == "__main__":
    {"frozen_state": frozen_state, "half_batch": half_batch,
     "frozen_from_epoch1": frozen_from_epoch1,
     "dies_in_window": dies_in_window,
     "none": lambda: None}[sys.argv[1]]()
    from benchmark import run

    sys.exit(run.main(sys.argv[2:]))
