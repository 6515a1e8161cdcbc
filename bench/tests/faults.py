"""Faults planted underneath the timed path, for the tests that drive a
whole run without the chip and see ``correct`` come out false. Each is a
hook ``fault(trainer)`` that ``run.run_cell`` calls once the trainer is
built; each breaks the round program before it is first traced."""
import jax
import jax.numpy as jnp


def state_unchanged(tr):
    """The round returns the server state it was given."""
    tr.engine._round_fn = lambda state, *args: state


def half_the_batch(tr):
    """Eq. 6 over the first half of the rows only, the mean taken over
    them."""
    eng = tr.engine
    agg = eng._aggregate

    def half(stacked, weights):
        m = weights.shape[0]
        return agg(stacked, weights * (jnp.arange(m) < m // 2))

    eng._aggregate = half


def answer_altered(tr):
    """The first row's answer (its delta, or its client's weights)
    negated where the row program produces it."""
    eng = tr.engine
    agg = eng._aggregate

    def altered(stacked, weights):
        return agg(jax.tree.map(lambda a: a.at[0].multiply(-1.0), stacked),
                   weights)

    eng._aggregate = altered


FAULTS = {"state_unchanged": state_unchanged,
          "half_the_batch": half_the_batch,
          "answer_altered": answer_altered}
