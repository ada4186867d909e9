"""Faults planted underneath the fifth sequence model's timed path, to
show that ``correct`` comes out false in ``route-lm-falcon-hybrid`` (as
``benchmark/faults_sala.py`` for ``route-lm-sala-long``). Each patches
the PROGRAM, never the harness, and each of the mixer's reaches both
forms of the scan:

- ``decay_constant``: a head's decay is the route's mean ``dt A`` at
  every real token, not its own token's;
- ``conv_sees_next``: the causal convolution is shifted one token on,
  so that a position reads the token after it;
- ``gate_after_norm``: the gated norm takes ``RMSNorm(y) * silu(z)``,
  the gate after the norm;
- ``other_group``: every head reads the B and C of the other group;
- ``multipliers_at_one``: the in-projection's five parts are left
  unmultiplied (``ssm_multipliers`` at 1);
- ``dt_on_padding``: ``dt`` is not zeroed at a padded position, so the
  state goes on past the route's last real token;
- ``causal_off_by_one``: an attention query sees the key after it too.
"""

from __future__ import annotations

from benchmark.faults import _patched


def decay_constant():
    import jax.numpy as jnp

    from routest_tpu.parallel import ssd

    real = ssd.log_decay

    def mean(dt, a):
        la = real(dt, a)
        live = dt > 0.0                 # a route's real tokens
        avg = jnp.where(live, la, 0.0).sum(1, keepdims=True) / jnp.maximum(
            live.sum(1, keepdims=True), 1)
        return jnp.where(live, avg, 0.0)

    return _patched(ssd, "log_decay", mean)


def conv_sees_next():
    import jax.numpy as jnp

    from routest_tpu.parallel import ssd

    real = ssd.causal_conv

    def ahead(x, w, b):
        return real(jnp.pad(x, ((0, 0), (0, 1), (0, 0)))[:, 1:], w, b)

    return _patched(ssd, "causal_conv", ahead)


def gate_after_norm():
    import jax
    import jax.numpy as jnp

    from routest_tpu.models import route_lm_falcon_h1

    def after(y, z, w, groups, eps):
        parts = y.astype(jnp.float32).reshape(y.shape[:-1] + (groups, -1))
        parts = parts * jax.lax.rsqrt(
            jnp.mean(parts * parts, -1, keepdims=True) + eps)
        out = (parts.reshape(y.shape) * w.astype(jnp.float32)
               * jax.nn.silu(z.astype(jnp.float32)))
        return out.astype(y.dtype)

    return _patched(route_lm_falcon_h1, "gated_rms_norm", after)


def other_group():
    from routest_tpu.parallel import ssd

    return _patched(ssd, "b_c_group", lambda head, heads, groups: (
        groups - 1) - head // (heads // groups))


def multipliers_at_one():
    from routest_tpu.models.route_lm_falcon_h1 import RouteLMFalconH1

    return _patched(RouteLMFalconH1, "ssm_multipliers",
                    lambda self: (1.0,) * 5)


def dt_on_padding():
    from routest_tpu.parallel import ssd

    return _patched(ssd, "live_step", lambda dt, live: dt)


def causal_off_by_one():
    from routest_tpu.parallel import gqa

    return _patched(gqa, "causal_keys",
                    lambda t_pos, s_pos: s_pos[None, :] <= t_pos[:, None] + 1)


FAULTS = {"decay_constant": decay_constant,
          "conv_sees_next": conv_sees_next,
          "gate_after_norm": gate_after_norm,
          "other_group": other_group,
          "multipliers_at_one": multipliers_at_one,
          "dt_on_padding": dt_on_padding,
          "causal_off_by_one": causal_off_by_one}
