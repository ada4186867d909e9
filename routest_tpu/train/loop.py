"""Training loop: the ``notebooks/`` capability the reference never built.

The reference repo gestures at "ML model training and evaluation" as
"Coming Soon" (``README.md:13-18``) and ships empty ``notebooks/`` and
``data/`` directories. This module is that missing training loop, done
TPU-first: a jitted/pjit-able train step (batch sharded over the mesh
``data`` axis, params replicated — pure data parallelism; XLA inserts the
gradient psum), optax AdamW, Huber loss, RMSE eval.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from routest_tpu.core.config import TrainConfig
from routest_tpu.core.mesh import MeshRuntime, pad_rows, pad_to_multiple
from routest_tpu.models.eta_mlp import EtaMLP, Params, fit_normalizer
from routest_tpu.data.features import batch_from_mapping
from routest_tpu.obs import get_registry
from routest_tpu.utils.logging import get_logger

_log = get_logger("routest_tpu.train")


class TrainState(NamedTuple):
    params: Params
    opt_state: optax.OptState
    step: jax.Array


class Batch(NamedTuple):
    features: jax.Array  # (B, 12)
    targets: jax.Array   # (B,) eta minutes
    weights: jax.Array   # (B,) 0/1 mask — padded rows get 0


def _decay_mask(params: Params):
    """Weight-decay only matrix weights: never the frozen normalizer stats
    (they receive no gradient, but decoupled decay would still erode them)
    and not biases."""
    return {
        "layers": [{"w": True, "b": False} for _ in params["layers"]],
        "norm": {"mean": False, "std": False},
    }


def make_optimizer(cfg: TrainConfig, total_steps: int = 1000) -> optax.GradientTransformation:
    warmup = max(1, min(100, total_steps // 10))
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=warmup,
        decay_steps=max(total_steps, warmup + 1),
        end_value=cfg.learning_rate * 0.05,
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, weight_decay=cfg.weight_decay, mask=_decay_mask),
    )


def loss_fn(model: EtaMLP, params: Params, batch: Batch) -> jax.Array:
    denom = jnp.maximum(batch.weights.sum(), 1.0)
    if getattr(model, "quantiles", ()):
        # Pinball (quantile) loss, averaged over the head axis: the unique
        # proper scoring rule whose minimizer is the target quantile, so
        # calibration is a property of convergence, not a regularizer.
        # the XLA body by name: the fused kernel EtaMLP.apply_quantiles
        # may choose on a TPU has no VJP
        pred = model.apply_quantiles_xla(params, batch.features)   # (B, Q)
        q = jnp.asarray(model.quantiles, pred.dtype)
        err = batch.targets[:, None] - pred
        per_row = jnp.maximum(q * err, (q - 1.0) * err).mean(axis=-1)
    else:
        pred = model.apply_xla(params, batch.features)
        # Huber on minutes: robust to the log-normal noise tail.
        per_row = optax.huber_loss(pred, batch.targets, delta=10.0)
    return (per_row * batch.weights).sum() / denom


def make_train_step(model: EtaMLP, optimizer: optax.GradientTransformation,
                    runtime: Optional[MeshRuntime] = None) -> Callable:
    """Build the jitted train step.

    With a ``MeshRuntime``, in/out shardings pin the batch to the data axis
    and the state replicated; XLA turns the grad reduction into a psum over
    ICI. Without one, plain jit (single device).
    """

    def step(state: TrainState, batch: Batch) -> Tuple[TrainState, jax.Array]:
        loss, grads = jax.value_and_grad(lambda p: loss_fn(model, p, batch))(state.params)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    if runtime is None:
        return jax.jit(step, donate_argnums=(0,))

    replicated = NamedSharding(runtime.mesh, P())
    batch_sh = NamedSharding(runtime.mesh, P(runtime.data_axis))
    return jax.jit(
        step,
        in_shardings=(replicated, Batch(batch_sh, batch_sh, batch_sh)),
        out_shardings=(replicated, replicated),
        donate_argnums=(0,),
    )


def make_eval_fn(model: EtaMLP, runtime: Optional[MeshRuntime] = None) -> Callable:
    """Masked sum-of-squared-error + count, for exact RMSE over padded shards."""

    def sse(params: Params, batch: Batch) -> Tuple[jax.Array, jax.Array]:
        # XLA by name here too: under a mesh the batch is sharded, and a
        # Mosaic kernel cannot be partitioned
        pred = model.apply_xla(params, batch.features)
        err = (pred - batch.targets) ** 2 * batch.weights
        return err.sum(), batch.weights.sum()

    if runtime is None:
        return jax.jit(sse)
    replicated = NamedSharding(runtime.mesh, P())
    batch_sh = NamedSharding(runtime.mesh, P(runtime.data_axis))
    return jax.jit(
        sse,
        in_shardings=(replicated, Batch(batch_sh, batch_sh, batch_sh)),
        out_shardings=(replicated, replicated),
    )


@functools.lru_cache(maxsize=16)
def _cached_eval_fn(model: EtaMLP, runtime: Optional[MeshRuntime]):
    """Eval functions are jitted once per (model, runtime); repeated rmse()
    calls (per-epoch eval) must not recompile."""
    return make_eval_fn(model, runtime)


def _minibatches(features: np.ndarray, targets: np.ndarray, batch_size: int,
                 rng: np.random.Generator, n_shards: int) -> Iterator[Batch]:
    n = len(targets)
    perm = rng.permutation(n)
    for start in range(0, n, batch_size):
        idx = perm[start:start + batch_size]
        rows = pad_to_multiple(len(idx), max(n_shards, 1))
        f = pad_rows(features[idx], rows)
        t = pad_rows(targets[idx], rows)
        w = pad_rows(np.ones(len(idx), np.float32), rows)
        yield Batch(jnp.asarray(f), jnp.asarray(t), jnp.asarray(w))


@dataclasses.dataclass
class FitResult:
    state: TrainState
    train_losses: list
    eval_rmse: float


def rmse(model: EtaMLP, params: Params, data: Dict[str, np.ndarray],
         runtime: Optional[MeshRuntime] = None, batch_size: int = 65536) -> float:
    """Exact RMSE of the model on a dataset dict (synthetic.py schema)."""
    features = batch_from_mapping(data)
    targets = np.asarray(data["eta_minutes"], np.float32)
    eval_fn = _cached_eval_fn(model, runtime)
    n_shards = runtime.n_data if runtime else 1
    total_sse, total_n = 0.0, 0.0
    n = len(targets)
    for start in range(0, n, batch_size):
        sl = slice(start, min(start + batch_size, n))
        rows = pad_to_multiple(sl.stop - sl.start, max(n_shards, 1))
        batch = Batch(
            jnp.asarray(pad_rows(features[sl], rows)),
            jnp.asarray(pad_rows(targets[sl], rows)),
            jnp.asarray(pad_rows(np.ones(sl.stop - sl.start, np.float32), rows)),
        )
        if runtime is not None:
            batch = Batch(*runtime.shard_batch(tuple(batch)))
        s, c = eval_fn(params, batch)
        total_sse += float(s)
        total_n += float(c)
    return float(np.sqrt(total_sse / max(total_n, 1.0)))


def fit(
    model: EtaMLP,
    train_data: Dict[str, np.ndarray],
    eval_data: Dict[str, np.ndarray],
    cfg: Optional[TrainConfig] = None,
    runtime: Optional[MeshRuntime] = None,
    log_every: int = 0,
) -> FitResult:
    """Full training run on a synthetic.py-schema dataset dict."""
    cfg = cfg or TrainConfig()
    features = batch_from_mapping(train_data)
    targets = np.asarray(train_data["eta_minutes"], np.float32)
    if len(targets) == 0:
        raise ValueError("fit: training set is empty")

    mean, std = fit_normalizer(features)
    key = jax.random.PRNGKey(cfg.seed)
    params = model.init(key, norm_mean=mean, norm_std=std)
    steps_per_epoch = max(1, (len(targets) + cfg.batch_size - 1) // cfg.batch_size)
    optimizer = make_optimizer(cfg, total_steps=cfg.epochs * steps_per_epoch)
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
    if runtime is not None:
        state = TrainState(*runtime.replicate(tuple(state)))

    start_epoch = 0
    if cfg.checkpoint_dir:
        from routest_tpu.train import checkpoint as ckpt

        found = ckpt.latest_checkpoint_step(cfg.checkpoint_dir)
        if found is not None:
            start_epoch, latest = found
            state = TrainState(*ckpt.restore_checkpoint(latest, tuple(state)))
            if runtime is not None:
                state = TrainState(*runtime.replicate(tuple(state)))
            if log_every:
                _log.info("train_resumed", checkpoint=latest,
                          epoch=start_epoch)

    step_fn = make_train_step(model, optimizer, runtime)
    n_shards = runtime.n_data if runtime else 1

    end_epoch = cfg.epochs
    if cfg.stop_after_epochs is not None:
        # Elastic/preemptible slice: this invocation trains a bounded
        # number of epochs of the FULL schedule (optimizer decay above
        # is built from cfg.epochs, so resumed slices stay on the
        # uninterrupted trajectory). 0 is a valid budget: restore,
        # train nothing, evaluate.
        if cfg.stop_after_epochs < 0:
            raise ValueError("stop_after_epochs must be >= 0")
        end_epoch = min(cfg.epochs, start_epoch + cfg.stop_after_epochs)

    losses = []
    saved_epoch = start_epoch  # nothing new to persist until we train
    # Train observability rides the same process-wide registry as
    # serving: per-epoch step time + loss are scrapeable/exportable
    # identically whether this runs in a notebook or under the server's
    # ensure-model bootstrap.
    reg = get_registry()
    m_epoch_s = reg.histogram("rtpu_train_epoch_seconds",
                              "Wall time per training epoch.")
    m_loss = reg.gauge("rtpu_train_loss", "Last epoch's training loss.")
    m_epochs = reg.counter("rtpu_train_epochs_total",
                           "Training epochs completed.")
    for epoch in range(start_epoch, end_epoch):
        t_epoch = time.perf_counter()
        # per-epoch rng: deterministic shuffles that are stable across a
        # resume (epoch k shuffles identically whether or not we restarted)
        rng = np.random.default_rng(cfg.seed + 1 + epoch)
        for batch in _minibatches(features, targets, cfg.batch_size, rng, n_shards):
            if runtime is not None:
                batch = Batch(*runtime.shard_batch(tuple(batch)))
            state, loss = step_fn(state, batch)
        losses.append(float(loss))
        epoch_s = time.perf_counter() - t_epoch
        m_epoch_s.observe(epoch_s)
        m_loss.set(losses[-1])
        m_epochs.inc()
        if log_every and (epoch + 1) % log_every == 0:
            _log.info("train_epoch", epoch=epoch + 1, epochs=cfg.epochs,
                      loss=round(losses[-1], 4),
                      epoch_seconds=round(epoch_s, 3))
        if (cfg.checkpoint_dir and cfg.checkpoint_every_epochs
                and (epoch + 1) % cfg.checkpoint_every_epochs == 0):
            from routest_tpu.train import checkpoint as ckpt

            ckpt.save_checkpoint(cfg.checkpoint_dir, epoch + 1, tuple(state))
            saved_epoch = epoch + 1

    if (cfg.checkpoint_dir and cfg.stop_after_epochs is not None
            and saved_epoch != end_epoch):
        # An elastic slice always persists its endpoint (including the
        # schedule-completing one): ending between periodic saves would
        # otherwise make the next invocation redo — and with a budget
        # below checkpoint_every_epochs, redo FOREVER — the work this
        # slice just did.
        from routest_tpu.train import checkpoint as ckpt

        ckpt.save_checkpoint(cfg.checkpoint_dir, end_epoch, tuple(state))

    eval_rmse = rmse(model, state.params, eval_data, runtime)
    return FitResult(state=state, train_losses=losses, eval_rmse=eval_rmse)
