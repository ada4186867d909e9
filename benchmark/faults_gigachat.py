"""Faults planted underneath the fourth sequence model's timed path, to
show that ``correct`` comes out false in ``route-lm-gigachat-dense`` (as
``benchmark/faults_kexaone.py`` for ``route-lm-kexaone-mixed``). Each
patches the PROGRAM, never the harness:

- ``groups_unlimited``: the router takes its top 8 over all 256 experts,
  no group cut off;
- ``yarn_left_out``: the rotary parts turn at the plain frequencies
  ``rope_theta ** (-2 i / 64)``, no pair stretched;
- ``mscale_left_out``: the softmax scale is ``192 ** -0.5``, without
  ``m(mscale_all_dim) ** 2``;
- ``scaling_left_at_one``: the routed experts' weights are not scaled by
  ``routed_scaling_factor``;
- ``causal_off_by_one``: a query sees the key after it too;
- ``module_fed_this_token``: the prediction module is given the
  embedding of ``id_t`` for that of ``id_{t+1}``;
- ``experts_dropped``: the held experts' terms are left out (only the
  shared expert is added): ``faults_seq``'s, the expert layer is one.
"""

from __future__ import annotations

from benchmark.faults import _patched
from benchmark.faults_seq import experts_dropped


def groups_unlimited():
    from routest_tpu.parallel import expert

    return _patched(expert, "keep_groups",
                    lambda score, n_group, topk_group: score)


def yarn_left_out():
    import numpy as np

    from routest_tpu.models import route_lm_gigachat

    def plain(dim, theta, scaling):
        return float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    return _patched(route_lm_gigachat, "yarn_inv_freq", plain)


def mscale_left_out():
    from routest_tpu.models import route_lm_gigachat

    return _patched(route_lm_gigachat, "yarn_mscale",
                    lambda factor, coefficient: 1.0)


def scaling_left_at_one():
    from routest_tpu.parallel import expert

    real = expert.route_top_k

    def unscaled(x, router, bias, top_k, scaling=1.0, **groups):
        return real(x, router, bias, top_k, 1.0, **groups)

    return _patched(expert, "route_top_k", unscaled)


def causal_off_by_one():
    from routest_tpu.parallel import latent

    return _patched(latent, "causal_keys",
                    lambda t_pos, s_pos: s_pos[None, :] <= t_pos[:, None] + 1)


def module_fed_this_token():
    from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat

    return _patched(RouteLMGigaChat, "mtp_input_ids", lambda self, ids: ids)


FAULTS = {"groups_unlimited": groups_unlimited,
          "yarn_left_out": yarn_left_out,
          "mscale_left_out": mscale_left_out,
          "scaling_left_at_one": scaling_left_at_one,
          "causal_off_by_one": causal_off_by_one,
          "module_fed_this_token": module_fed_this_token,
          "experts_dropped": experts_dropped}
