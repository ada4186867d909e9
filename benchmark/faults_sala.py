"""Faults planted underneath the second sequence model's timed path, to
show that ``correct`` comes out false in ``route-lm-sala-long`` (as
``benchmark/faults_seq.py`` for ``route-lm-score``). Each patches the
PROGRAM, never the harness:

- ``recent_blocks``: the learned choice is replaced by the ``topk`` most
  recent blocks;
- ``decay_without_layer``: the linear mixer's decay leaves out its
  layer factor (every layer decays as layer 0 would);
- ``initial_not_forced``: the first block is not forced;
- ``compressed_early``: a compressed key is visible one stride early (a
  look at the future).
"""

from __future__ import annotations

from benchmark.faults import _patched


def recent_blocks():
    import jax.numpy as jnp

    from routest_tpu.parallel import select

    def recent(scores, t_pos, *, top, block, init, local):
        m = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
        last = (t_pos // block)[:, None]
        mask = (m <= last) & (m > last - top)
        return jnp.broadcast_to(mask[None], scores.shape)

    return _patched(select, "choose_blocks", recent)


def decay_without_layer():
    from routest_tpu.parallel import linear_attn

    real = linear_attn.log_decay

    def flat(heads, layer, n_layers):
        return real(heads, 0, n_layers)

    return _patched(linear_attn, "log_decay", flat)


def initial_not_forced():
    from routest_tpu.parallel import select

    real = select.forced_blocks

    def local_only(t_pos, n_blocks, block, init, local):
        return real(t_pos, n_blocks, block, 0, local)

    return _patched(select, "forced_blocks", local_only)


def compressed_early():
    from routest_tpu.parallel import select

    real = select.compressed_visible

    def early(t_pos, n_comp, window, stride):
        return real(t_pos + stride, n_comp, window, stride)

    return _patched(select, "compressed_visible", early)


FAULTS = {"recent_blocks": recent_blocks,
          "decay_without_layer": decay_without_layer,
          "initial_not_forced": initial_not_forced,
          "compressed_early": compressed_early}
