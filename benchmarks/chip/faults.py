"""Faults planted underneath the executor's decode step, to show that the
comparison in ``correct.py`` calls such a run not correct.  Each wraps
``coded_pool_decode_step``; the CPU tests plant them at the program's
reduced size and ``calibrate.py --fault`` on the chip at a cell's own.
"""

import jax.numpy as jnp


def state_unchanged(step):
    """The step returns the pool state it was given."""
    def broken(cfg, coding, params, state, *args, **kwargs):
        out, _, report = step(cfg, coding, params, state, *args, **kwargs)
        return out, state, report
    return broken


def half_left_out(step):
    """Half of every group's query rows are never computed: they come
    back as the id a zero row of logits samples to."""
    def broken(cfg, coding, params, state, *args, **kwargs):
        out, new, report = step(cfg, coding, params, state, *args, **kwargs)
        rows = jnp.arange(out.shape[0]) % coding.k
        return jnp.where(rows >= coding.k // 2, 0, out), new, report
    return broken


def token_altered(step):
    """Every served id is the next id of the vocabulary."""
    def broken(cfg, coding, params, state, *args, **kwargs):
        out, new, report = step(cfg, coding, params, state, *args, **kwargs)
        return (out + 1) % cfg.vocab_size, new, report
    return broken


FAULTS = {"state_unchanged": state_unchanged,
          "half_left_out": half_left_out,
          "token_altered": token_altered}


def plant(name: str) -> None:
    """Break the program's decode step, for the rest of the process."""
    from repro.serving import continuous
    continuous.coded_pool_decode_step = FAULTS[name](
        continuous.coded_pool_decode_step)
