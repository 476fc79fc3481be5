"""Maximum flow and the canonical minimum cut of an s-t network.

The network of an instance: a source s with an arc s -> v of capacity
``excess[v]``, a sink t with an arc v -> t of capacity ``sink_cap[v]``,
and for each edge (u, v) the arcs u -> v of ``cap_fwd`` and v -> u of
``cap_bwd``.  The maximum flow comes from SciPy's Dinic solver
(``scipy.sparse.csgraph.maximum_flow``); the cut is the set T of vertices
that reach t in the residual graph of that flow.  T is the same for every
maximum flow and every maximum preflow (it is the sink side of the minimum
cut with the smallest sink side), so the cut of any correct solver that
reports "reaches t in its residual graph" equals it exactly.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import breadth_first_order, maximum_flow


def _network(inst: dict, caps=None):
    n = inst["n"]
    s, t = n, n + 1
    e = inst["edges"]
    cf, cb, ex, sk = caps if caps is not None else (
        inst["cap_fwd"], inst["cap_bwd"], inst["excess"], inst["sink_cap"])
    v = np.arange(n)
    rows = np.concatenate([e[:, 0], e[:, 1], np.full(n, s), v])
    cols = np.concatenate([e[:, 1], e[:, 0], v, np.full(n, t)])
    data = np.concatenate([cf, cb, ex, sk]).astype(np.int32)
    keep = data > 0
    cap = sp.csr_matrix((data[keep], (rows[keep], cols[keep])),
                        shape=(n + 2, n + 2), dtype=np.int32)
    return cap, s, t


def _solve(cap, s, t, n):
    res = maximum_flow(cap, s, t, method="dinic")
    flow = res.flow.tocsr()
    # residual of each ordered pair: capacity minus (antisymmetric) flow
    resid = (cap.astype(np.int64) - flow.astype(np.int64)).tocsr()
    resid.data[resid.data < 0] = 0
    resid.eliminate_zeros()
    # T: vertices with a residual path to t = reached from t on reversed arcs
    reach = breadth_first_order(resid.T.tocsr(), t, directed=True,
                                return_predecessors=False)
    sink_side = np.zeros(n + 2, dtype=bool)
    sink_side[reach] = True
    return int(res.flow_value), ~sink_side[:n]


def min_cut(inst: dict) -> tuple[int, np.ndarray]:
    """(maximum flow value, source side bool[n]) of ``inst``."""
    cap, s, t = _network(inst)
    return _solve(cap, s, t, inst["n"])


def min_cut_quantized(inst: dict, bits: int = 8) -> tuple[int, np.ndarray]:
    """The same solve with every capacity held in ``bits`` bits: scaled by
    one factor per instance so that the largest fits, rounded, solved, and
    the flow scaled back.  The benchmark's control: a solver that stores
    capacities in a narrower type than the configuration states."""
    arrays = [np.asarray(inst[k], np.int64)
              for k in ("cap_fwd", "cap_bwd", "excess", "sink_cap")]
    top = max(int(a.max()) for a in arrays if a.size)
    scale = max(1.0, top / (2 ** (bits - 1) - 1))
    q = [np.rint(a / scale).astype(np.int32) for a in arrays]
    cap, s, t = _network(inst, q)
    flow, source = _solve(cap, s, t, inst["n"])
    return int(round(flow * scale)), source
