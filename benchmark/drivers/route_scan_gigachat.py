"""Closed-loop passes over a resident table of route histories, scored
by the fourth route-sequence model (``RouteLMGigaChat``, configuration
``gigachat3.1-702b-ep16``) through the same table-scoring entry as
``route_scan.py`` drives: the set-up, the window, the warm-up, the
end-to-end metric and the form of what is compared are
``route_scan_kexaone.py``'s; this one puts the other model and its
count of the work in and compares with its reference.

``correct``: after the window the plain float32 reference
(``benchmark/reference/gigachat_ref.py``) recomputes every route of the
last timed pass, one route at a time, and what that pass wrote is
compared with it: the nine gaps of ``route_scan_kexaone.gaps`` (the
four of the first column, the three of the prediction module's column
over a route's n - 1 positions, ``expert_gap`` over the expert blocks'
(token, slot) choices, the module's among them, and ``key_set_gap``:
the share of (block, token) whose number of keys seen differs, exactly
0: no layer here is a sliding one), but ``rows_gap`` over ALL the
table's named rows as one array and not the worst route's four: here a
named row moves by a whole expert's term where a near-tie of the
bfloat16 router flips at that one token, and the worst of ten draws of
four rows is as noisy as that event is rare (0.012-0.104 over 13 seeds:
no limit stands between that and the control's 0.19 with room).
"""

from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat  # noqa: I001
# (the program's entry first: a commit without it fails here, at once)

from typing import Dict, List

import numpy as np

from benchmark import counts_gigachat
from benchmark.drivers import route_scan_kexaone
from benchmark.faults import _patched
from benchmark.reference import gigachat_ref
from benchmark.reference.dots3_ref import Blocks

ANNOTATIONS = route_scan_kexaone.ANNOTATIONS


class Driver(route_scan_kexaone.Driver):
    def __init__(self, run) -> None:
        """The base driver's set-up with this model's class where it
        names its own: it builds whatever class it finds under that
        name (``from_config``, the policy's dtypes, ``mtp_held``, the
        share, the scorer), and has no other hook."""
        from routest_tpu.models import route_lm_kexaone

        with _patched(route_lm_kexaone, "RouteLMKExaone", RouteLMGigaChat):
            super().__init__(run)

    def counts(self) -> Dict:
        """The base driver's (the module's block is the last row of
        ``chosen`` and holds a route's n - 1 tokens), with this model's
        FLOPs where it names its own model's."""
        with _patched(route_scan_kexaone.counts_kexaone, "pass_flops",
                      counts_gigachat.pass_flops):
            return super().counts()

    # ── the comparison ──────────────────────────────────────────────

    def reference(self, precision: str = "") -> List[Dict]:
        blocks = Blocks(**self.mix["reference_blocks"])
        out = []
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append(gigachat_ref.forward(
                self.params, self.cfg, self.table["ids"][r, :n], self.share,
                list(self.table["rows_at"][r]), blocks=blocks,
                precision=precision or None))
        return out

    def gaps(self, got: List[Dict], want: List[Dict]) -> Dict[str, float]:
        out = route_scan_kexaone.gaps(got, want, sliding=[])
        out["rows_gap"] = route_scan_kexaone.rel_gap(
            np.concatenate([g["rows"] for g in got]),
            np.concatenate([w["rows"] for w in want]))
        return out
