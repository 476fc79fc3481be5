"""The plain reference agrees with known flows and with networkx."""

from __future__ import annotations

import json

import networkx as nx
import numpy as np
import pytest

from bench import families
from bench.reference import min_cut, min_cut_quantized
from bench.tests.helpers import ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _networkx_flow(inst):
    g = nx.DiGraph()
    for (u, v), a, b in zip(inst["edges"], inst["cap_fwd"], inst["cap_bwd"]):
        g.add_edge(int(u), int(v), capacity=int(a))
        g.add_edge(int(v), int(u), capacity=int(b))
    for v in range(inst["n"]):
        if inst["excess"][v]:
            g.add_edge("s", v, capacity=int(inst["excess"][v]))
        if inst["sink_cap"][v]:
            g.add_edge(v, "t", capacity=int(inst["sink_cap"][v]))
    value, _ = nx.maximum_flow(g, "s", "t")
    return value


def test_known_small_network():
    # s->0 (5), s->1 (3); 0->1 (2), 1->0 (0); 0->t (1), 1->t (9)
    inst = dict(n=2, edges=np.array([[0, 1]]), cap_fwd=np.array([2]),
                cap_bwd=np.array([0]), excess=np.array([5, 3]),
                sink_cap=np.array([1, 9]))
    flow, source = min_cut(inst)
    assert flow == 6                   # 1 + 2 through vertex 0, 3 from 1
    # 0 keeps residual excess that cannot reach t: source side {0};
    # 1 reaches t through its unsaturated t-link
    assert source.tolist() == [True, False]


def test_cut_is_the_smallest_sink_side():
    # two minimum cuts of equal cost: the reference names the one whose
    # sink side is smallest (the vertices that still reach t)
    inst = dict(n=2, edges=np.array([[0, 1]]), cap_fwd=np.array([4]),
                cap_bwd=np.array([0]), excess=np.array([4, 0]),
                sink_cap=np.array([0, 4]))
    flow, source = min_cut(inst)
    assert flow == 4
    assert source.tolist() == [True, True]


@pytest.mark.parametrize("config", ["synth2d-8c", "seg2d-seeds"])
@pytest.mark.parametrize("seed", [0, 2**31 + 7, 4294967311])
def test_agrees_with_networkx(config, seed):
    inst = families.make(_config(config), (9, 11), families.rng_for(seed, 1))
    flow, source = min_cut(inst)
    assert flow == _networkx_flow(inst)
    # the source side's cut costs exactly the flow
    s = source
    e = inst["edges"]
    cost = (inst["excess"][~s].sum() + inst["sink_cap"][s].sum()
            + inst["cap_fwd"][s[e[:, 0]] & ~s[e[:, 1]]].sum()
            + inst["cap_bwd"][s[e[:, 1]] & ~s[e[:, 0]]].sum())
    assert cost == flow


@pytest.mark.parametrize("config", ["synth2d-8c", "seg2d-seeds"])
def test_quantized_control_differs(config):
    """The control (8-bit capacities) reads a different flow on the
    families the benchmark runs."""
    differ = 0
    for seed in range(4):
        inst = families.make(_config(config), (16, 16),
                             families.rng_for(seed, 1))
        differ += min_cut_quantized(inst)[0] != min_cut(inst)[0]
    assert differ >= 3
