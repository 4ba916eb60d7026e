"""Layout-quality regression gate.

``quality_report`` (NELD / CRE / sampled stress) on the CI-sized
RegularGraphs suite, asserted against recorded bounds. The bounds are the
values measured at the time this gate was recorded (PR 4, seed=0,
deterministic on the CPU backend) times a generous slack factor — future
PRs can refactor the driver freely but cannot *silently* degrade drawing
quality past the slack.

If a deliberate algorithm change moves a metric past its bound, re-record:

    PYTHONPATH=src python -m pytest tests/test_quality_regression.py -s \
        --tb=no  # the failure message prints measured vs bound
"""
import numpy as np
import pytest

from repro.graphs import generators as G, build_graph
from repro.graphs.metrics import quality_report
from repro.core import multigila_layout, LayoutConfig


# measured with LayoutConfig(seed=0) on the jax-cpu backend at record time;
# asserted with slack: neld ≤ 1.4·rec + 0.05, cre ≤ 1.5·rec + 0.1,
# stress ≤ 1.6·rec + 0.01
RECORDED = {
    "grid_8_8":   dict(neld=0.136, cre=0.000, stress=0.0226),
    "tree_3_3":   dict(neld=0.439, cre=0.000, stress=0.0830),
    "cyl_8_6":    dict(neld=0.198, cre=0.682, stress=0.0933),
    "sierp_3":    dict(neld=0.165, cre=0.000, stress=0.0115),
    "snow_3_2_1": dict(neld=0.401, cre=0.000, stress=0.0503),
    "spider_4_5": dict(neld=0.207, cre=0.154, stress=0.0511),
    "flower_4_5": dict(neld=0.521, cre=1.467, stress=0.0897),
    "rnd_64_4":   dict(neld=0.322, cre=4.065, stress=0.1827),
}

# the maxent-stress engine (core/stress.py) scored on the SAME suite, same
# seed/backend — recorded at PR 10. Stress wins NELD almost everywhere
# (meshes dramatically: grid 0.037 vs 0.136, cylinder 0.005 vs 0.198) and
# trades some CRE on the irregular graphs; the gate holds both engines to
# their own recorded envelope. spider_4_5 is re-recorded on JAX 0.9.0,
# where the seed-0 layout rounds differently (CRE 0.462 against 0.231 on
# 0.4.37): that reading lies inside the spread of seeds 0-7 on 0.9.0 (CRE
# 0.231-0.615, stress 0.075-0.129, NELD 0.0018-0.0042), so the engine did
# not regress.
RECORDED_STRESS = {
    "grid_8_8":   dict(neld=0.037, cre=0.000, stress=0.0205),
    "tree_3_3":   dict(neld=0.354, cre=0.308, stress=0.0759),
    "cyl_8_6":    dict(neld=0.005, cre=0.818, stress=0.1118),
    "sierp_3":    dict(neld=0.108, cre=2.000, stress=0.1129),
    "snow_3_2_1": dict(neld=0.299, cre=0.000, stress=0.0379),
    "spider_4_5": dict(neld=0.004, cre=0.462, stress=0.1286),
    "flower_4_5": dict(neld=0.280, cre=3.533, stress=0.1158),
    "rnd_64_4":   dict(neld=0.058, cre=5.371, stress=0.1889),
}

SUITE = G.regulargraphs_suite(small=True)


def _check(name, e, n, engine, rec):
    pos, _ = multigila_layout(e, n, LayoutConfig(seed=0, engine=engine))
    g = build_graph(e, n)
    p = np.zeros((g.n_pad, 2), np.float32)
    p[:n] = pos
    rep = quality_report(g, p)
    bounds = dict(neld=1.4 * rec["neld"] + 0.05,
                  cre=1.5 * rec["cre"] + 0.1,
                  stress=1.6 * rec["stress"] + 0.01)
    for metric, bound in bounds.items():
        assert rep[metric] <= bound, (
            f"{name}.{metric} [{engine}] regressed: measured "
            f"{rep[metric]:.4f} > bound {bound:.4f} "
            f"(recorded {rec[metric]:.4f})")


@pytest.mark.parametrize("name,e,n", SUITE, ids=[s[0] for s in SUITE])
def test_quality_no_regression(name, e, n):
    _check(name, e, n, "gila", RECORDED[name])


@pytest.mark.parametrize("name,e,n", SUITE, ids=[s[0] for s in SUITE])
def test_quality_no_regression_stress(name, e, n):
    _check(name, e, n, "stress", RECORDED_STRESS[name])
