"""Pure reference oracles.

* ``maxflow_oracle`` — plain numpy BFS augmenting-path (Edmonds-Karp)
  maxflow on the excess/sink-cap problem representation.  Ground truth for
  every solver and kernel test.
* ``push_relabel_iteration_ref`` — pure-jnp oracle for the Pallas
  push-relabel kernel (kernels/push_relabel.py).
* ``fused_iteration_ref`` — pure-jnp oracle for one *complete* fused engine
  iteration (push compute + intra-region scatter + post-push relabel), the
  unit the region-resident fused kernel advances per in-kernel step.  It
  keeps the push-side scatter form of the apply; the engine pulls
  (``kernels.push_relabel.make_push_apply``), so the two agree exactly on
  layouts whose ``rev_slot`` pairs intra arcs.
* ``attention_ref`` — pure-jnp oracle for the Pallas flash-attention kernel.
"""

from __future__ import annotations

from collections import deque

import jax.numpy as jnp
import numpy as np

INF_LABEL = 2**30


def maxflow_oracle(problem) -> tuple[int, np.ndarray]:
    """Edmonds-Karp on the terminal-capacity representation.

    Returns (maxflow value, source_side bool[n]) where source_side is the
    minimal source set {v : s -> v in G_f} complement of T.
    """
    n = problem.num_vertices
    # explicit s = n, t = n + 1
    s, t = n, n + 1
    cap = {}

    def add(u, v, c):
        if c:
            cap[(u, v)] = cap.get((u, v), 0) + int(c)

    for (u, v), cf_, cb_ in zip(problem.edges, problem.cap_fwd,
                                problem.cap_bwd):
        add(int(u), int(v), cf_)
        add(int(v), int(u), cb_)
    for v in range(n):
        add(s, v, problem.excess[v])
        add(v, t, problem.sink_cap[v])

    adj = [[] for _ in range(n + 2)]
    for (u, v) in list(cap.keys()):
        adj[u].append(v)
        adj[v].append(u)
        cap.setdefault((v, u), 0)
    adj = [sorted(set(a)) for a in adj]

    flow = 0
    while True:
        parent = {s: s}
        q = deque([s])
        while q and t not in parent:
            u = q.popleft()
            for v in adj[u]:
                if v not in parent and cap.get((u, v), 0) > 0:
                    parent[v] = u
                    q.append(v)
        if t not in parent:
            break
        # bottleneck
        path = []
        v = t
        while v != s:
            path.append((parent[v], v))
            v = parent[v]
        aug = min(cap[(u, v)] for u, v in path)
        for u, v in path:
            cap[(u, v)] -= aug
            cap[(v, u)] += aug
        flow += aug
    # source side of the min cut
    seen = {s}
    q = deque([s])
    while q:
        u = q.popleft()
        for v in adj[u]:
            if v not in seen and cap.get((u, v), 0) > 0:
                seen.add(v)
                q.append(v)
    side = np.zeros(n, dtype=bool)
    for v in range(n):
        side[v] = v in seen
    return flow, side


def push_relabel_iteration_ref(cf, sink_cf, excess, lab, nbr, rev_slot,
                               intra, emask, vmask, cross_lab, cross_pushable,
                               d_inf):
    """One synchronous push+relabel iteration — pure jnp, mirrors engine.body.

    This is the oracle for the Pallas kernel, which computes the push deltas
    and relabel values for a block of vertices.
    """
    V, E = cf.shape
    act = (excess > 0) & (lab < d_inf) & vmask
    nlab = jnp.where(intra, lab[nbr], cross_lab)
    nlab = jnp.where((cross_pushable | intra) & emask, nlab, INF_LABEL)
    adm = (cf > 0) & (lab[:, None] == nlab + 1) & act[:, None]
    sink_adm = (sink_cf > 0) & (lab == 1) & act
    sink_cap = jnp.where(sink_adm, sink_cf, 0)
    arc_cap = jnp.where(adm, cf, 0)
    caps = jnp.concatenate([sink_cap[:, None], arc_cap], axis=1)
    avail = jnp.where(act, excess, 0)
    cum_excl = jnp.cumsum(caps, axis=1) - caps
    delta = jnp.clip(avail[:, None] - cum_excl, 0, caps)
    # relabel candidates on the *post push* residual state are computed by
    # the caller; the kernel itself emits deltas + the relabel min on the
    # pre-push state for vertices with no admissible arc.
    no_adm = act & ~adm.any(axis=1) & ~sink_adm
    cand = jnp.where(cf > 0, nlab + 1, INF_LABEL).min(axis=1)
    cand = jnp.where(sink_cf > 0, jnp.minimum(cand, 1), cand)
    new_lab = jnp.where(no_adm, jnp.maximum(jnp.minimum(cand, d_inf), lab),
                        lab)
    return delta, new_lab


def fused_iteration_ref(cf, sink_cf, excess, lab, nbr, rev_slot, intra,
                        emask, vmask, cross_lab, cross_pushable, d_inf,
                        sink_open: bool = True):
    """One complete fused engine iteration — pure jnp oracle.

    push compute (labels frozen) -> scatter application of the deltas
    (reverse arcs + receiver excess for intra arcs; cross flow accumulated
    into ``out_push``) -> relabel on the post-push residual graph.  This is
    the per-step unit of the region-resident fused kernel and of the fused
    XLA engine body; both are tested bit-equal against it.

    Returns ``(cf, sink_cf, excess, new_lab, out_push, sink_pushed,
    relabel_sum)``.
    """
    V, E = cf.shape
    sink = sink_cf if sink_open else jnp.zeros_like(sink_cf)
    delta, _ = push_relabel_iteration_ref(
        cf, sink, excess, lab, nbr, rev_slot, intra, emask, vmask, cross_lab,
        cross_pushable, d_inf)
    d_sink = delta[:, 0]
    d_arc = delta[:, 1:]
    excess = excess - d_sink - jnp.sum(d_arc, axis=1, dtype=d_arc.dtype)
    sink_cf = sink_cf - d_sink
    cf = cf - d_arc
    d_intra = jnp.where(intra, d_arc, 0)
    flat_n = V * E
    flat_idx = (nbr * E + rev_slot).reshape(flat_n)
    cf = (cf.reshape(flat_n).at[flat_idx]
          .add(d_intra.reshape(flat_n), mode="drop").reshape(V, E))
    excess = excess + jnp.zeros((V,), excess.dtype).at[nbr.reshape(flat_n)] \
        .add(d_intra.reshape(flat_n), mode="drop")
    out_push = d_arc - d_intra
    sink2 = sink_cf if sink_open else jnp.zeros_like(sink_cf)
    _, new_lab = push_relabel_iteration_ref(
        cf, sink2, excess, lab, nbr, rev_slot, intra, emask, vmask, cross_lab,
        cross_pushable, d_inf)
    relabel_sum = jnp.sum(jnp.where(vmask, new_lab - lab, 0))
    return (cf, sink_cf, excess, new_lab, out_push, d_sink.sum(),
            relabel_sum)


def attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    """Numerically-stable reference attention (f32 accumulation)."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("...qd,...kd->...qk", qf, kf) * scale
    if causal:
        Tq, Tk = logits.shape[-2:]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        logits = jnp.where(mask, logits, -1e30)
    probs = jnp.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    return jnp.einsum("...qk,...kd->...qd", probs, vf).astype(q.dtype)
