"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence models and no parallelism of any kind
(SURVEY.md §5.7 — its longest "sequence" is a polyline walked in Python
lists, reference ``Flaskr/utils.py:162-167``). Here the long axis is a
route: a delivery run expressed as a sequence of legs/polyline points,
potentially far longer than one chip's HBM wants to hold at attention's
O(S²) cost. This package scales that axis across the mesh:

- :mod:`routest_tpu.parallel.ring` — ring attention: K/V blocks rotate
  around the ICI ring via ``lax.ppermute`` while each device accumulates
  its queries' attention with a running (online) softmax;
- :mod:`routest_tpu.parallel.ulysses` — all-to-all sequence parallelism:
  ``lax.all_to_all`` re-shards sequence↔heads so every device runs full
  attention over a head shard;
- :mod:`routest_tpu.parallel.tensor` — Megatron column/row tensor
  parallelism over the ``model`` mesh axis (forward, training, serving);
- :mod:`routest_tpu.parallel.pipeline` — GPipe fill-drain pipeline
  parallelism mapping model stages onto a ``stage`` mesh axis;
- :mod:`routest_tpu.parallel.expert` — the expert layer: top-k routed
  experts with a shared one, computed for the share of the experts one
  chip holds (a grouped product over uneven groups), and Switch-style
  expert parallelism (capacity-bounded all_to_all dispatch) over an
  ``expert`` axis;
- :mod:`routest_tpu.parallel.select` — causal attention over a window
  of keys and over a learned selection of keys, a block of queries at a
  time (one device; what the route-sequence language model runs).

All but the last two's one-chip parts are pure shard_map programs — XLA
emits the collectives over ICI; gradients flow through them, so the
same code paths train.
"""

from routest_tpu.parallel.expert import (init_moe_params, make_moe_apply,
                                         shard_moe_params)
from routest_tpu.parallel.pipeline import (make_pipeline_apply,
                                           make_pipeline_train_step,
                                           microbatch, shard_stage_params,
                                           stack_stage_params)
from routest_tpu.parallel.ring import ring_attention, ring_attention_sharded
from routest_tpu.parallel.tensor import (make_tp_apply, make_tp_train_step,
                                         shard_tp_params)
from routest_tpu.parallel.ulysses import ulysses_attention, ulysses_attention_sharded

__all__ = [
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
    "make_tp_apply",
    "make_tp_train_step",
    "shard_tp_params",
    "make_pipeline_apply",
    "make_pipeline_train_step",
    "microbatch",
    "stack_stage_params",
    "shard_stage_params",
    "init_moe_params",
    "make_moe_apply",
    "shard_moe_params",
]
