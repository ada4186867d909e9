"""Faults planted underneath the sequence scorer's timed path, to show
that ``correct`` comes out false (as ``benchmark/faults.py`` for the two
older cells). Each patches the PROGRAM, never the harness:

- ``experts_dropped``: the held experts' terms are left out of the
  expert layer (only the shared expert is added);
- ``recent_selected``: the learned selection is replaced by the most
  recent ``index_topk`` keys;
- ``rescale_left_out``: the latents' scale correction is not applied;
- ``window_off_by_one``: a sliding layer sees one key fewer.
"""

from __future__ import annotations

import dataclasses

from benchmark.faults import _patched


def experts_dropped():
    import jax.numpy as jnp

    from routest_tpu.parallel import expert

    def none(x, chosen, weights, experts, share, valid=None, tile=None):
        return (jnp.zeros(x.shape, jnp.float32),
                jnp.zeros((share.count,), jnp.int32))

    return _patched(expert, "grouped_experts", none)


def recent_selected():
    import jax.numpy as jnp

    from routest_tpu.parallel import select

    def recent(scores, t_pos, top_k):
        s = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
        return (s <= t_pos[:, None]) & (s > t_pos[:, None] - top_k)

    return _patched(select, "top_k_mask", recent)


def _sizes_altered(change):
    from routest_tpu.models.route_lm import RouteLM

    real = RouteLM.attention_sizes

    def altered(self, kind):
        return change(real(self, kind))

    return _patched(RouteLM, "attention_sizes", altered)


def rescale_left_out():
    return _sizes_altered(
        lambda a: dataclasses.replace(a, s_q=1.0, s_kv=1.0))


def window_off_by_one():
    return _sizes_altered(
        lambda a: dataclasses.replace(a, window=a.window - 1)
        if a.window else a)


FAULTS = {"experts_dropped": experts_dropped,
          "recent_selected": recent_selected,
          "rescale_left_out": rescale_left_out,
          "window_off_by_one": window_off_by_one}
