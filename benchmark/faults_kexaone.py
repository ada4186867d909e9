"""Faults planted underneath the third sequence model's timed path, to
show that ``correct`` comes out false in ``route-lm-kexaone-mixed`` (as
``benchmark/faults_seq.py`` for ``route-lm-score``). Each patches the
PROGRAM, never the harness:

- ``scaling_left_at_one``: the routed experts' weights are not scaled by
  ``routed_scaling_factor``;
- ``window_off_by_one``: a sliding layer sees one key fewer;
- ``rope_on_full``: the full layers rotate their queries and keys too;
- ``head_modulo_group``: query head h reads key-value head ``h % G``,
  not ``h // (H / G)`` (and the heads reach ``W_o`` in that order);
- ``module_fed_this_token``: the prediction module is given the
  embedding of ``id_t`` for that of ``id_{t+1}``;
- ``experts_dropped``: the held experts' terms are left out (only the
  shared expert is added): ``faults_seq``'s, the expert layer is one.
"""

from __future__ import annotations

from benchmark.faults import _patched
from benchmark.faults_seq import experts_dropped


def scaling_left_at_one():
    from routest_tpu.parallel import expert

    real = expert.route_top_k

    def unscaled(x, router, bias, top_k, scaling=1.0):
        return real(x, router, bias, top_k, 1.0)

    return _patched(expert, "route_top_k", unscaled)


def window_off_by_one():
    from routest_tpu.parallel import gqa

    real = gqa.window_keys

    def narrower(t_pos, s_pos, window):
        return real(t_pos, s_pos, window - 1)

    return _patched(gqa, "window_keys", narrower)


def rope_on_full():
    from routest_tpu.models.route_lm_kexaone import RouteLMKExaone

    return _patched(RouteLMKExaone, "uses_rope", lambda self, kind: True)


def head_modulo_group():
    from routest_tpu.models import route_lm_kexaone

    def interleaved(q, groups):
        b_sz, length, heads, d = q.shape
        return q.reshape(b_sz, length, heads // groups, groups,
                         d).transpose(0, 1, 3, 2, 4)

    return _patched(route_lm_kexaone, "by_group", interleaved)


def module_fed_this_token():
    from routest_tpu.models.route_lm_kexaone import RouteLMKExaone

    return _patched(RouteLMKExaone, "mtp_input_ids", lambda self, ids: ids)


FAULTS = {"scaling_left_at_one": scaling_left_at_one,
          "window_off_by_one": window_off_by_one,
          "rope_on_full": rope_on_full,
          "head_modulo_group": head_modulo_group,
          "module_fed_this_token": module_fed_this_token,
          "experts_dropped": experts_dropped}
