"""Faults planted underneath the timed path, to show that ``correct``
comes out false. Used by the tests (toy sizes, CPU) and by
``tools/readings.py`` (the cells' own sizes, on the chip). Each is a
context manager that patches the PROGRAM, never the harness:

- ``answer_altered``: the scorer's answers are 3% high on every 997th
  row of a slice (an answer altered where it is produced);
- ``rows_left_out``: the scorer leaves the second half of every slice
  unscored (zeros), as a kernel with a wrong grid would;
- ``state_unchanged``: the train step returns its parameters unchanged;
- ``half_batch``: the loss leaves out every second observed arc and
  takes the mean over the rest.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def answer_altered():
    import jax.numpy as jnp

    from routest_tpu.models.eta_mlp import EtaMLP

    real = EtaMLP.apply_quantiles

    def altered(self, params, x):
        y = real(self, params, x)
        hit = (jnp.arange(y.shape[0]) % 997 == 0)[:, None]
        return jnp.where(hit, y * 1.03, y)

    return _patched(EtaMLP, "apply_quantiles", altered)


def rows_left_out():
    import jax.numpy as jnp

    from routest_tpu.models.eta_mlp import EtaMLP

    real = EtaMLP.apply_quantiles

    def half(self, params, x):
        y = real(self, params, x)
        keep = (jnp.arange(y.shape[0]) < y.shape[0] // 2)[:, None]
        return jnp.where(keep, y, 0.0)

    return _patched(EtaMLP, "apply_quantiles", half)


def state_unchanged():
    import optax

    return _patched(optax, "apply_updates", lambda params, updates: params)


def half_batch():
    import jax.numpy as jnp

    from routest_tpu.models.gnn import RoadGNN

    real = RoadGNN.loss

    def loss(self, params, node_coords, batch, combine=lambda x: x,
             reduce=lambda x: x, loss_weights=None):
        lw = batch.weights if loss_weights is None else loss_weights
        keep = (jnp.cumsum(lw) % 2 == 1).astype(lw.dtype)
        return real(self, params, node_coords, batch, combine=combine,
                    reduce=reduce, loss_weights=lw * keep)

    return _patched(RoadGNN, "loss", loss)


FAULTS = {"answer_altered": answer_altered, "rows_left_out": rows_left_out,
          "state_unchanged": state_unchanged, "half_batch": half_batch}
