"""Region-partitioned flow-network representation.

The paper (Shekhovtsov & Hlavac 2011) partitions the vertex set of a sparse
network into K regions; every discharge operation touches exactly one region's
subnetwork plus its boundary.  On TPU we mirror that structure directly:

* vertices are stored region-blocked, padded to a common region size ``V``;
* adjacency is a padded ELL layout ``[K, V, E]`` (``E`` = max degree) so that
  every per-vertex operation is a dense, vectorizable row operation;
* the source is eliminated by the paper's ``Init`` (saturate all (s,v) edges
  -> per-vertex ``excess``), the sink is kept as an implicit 0-labelled
  vertex reachable through a per-vertex terminal capacity ``sink_cf``;
* every *directed* residual arc (u,v) lives in u's row.  A cross-region arc
  (u,v), part(u)=r != q=part(v), therefore lives in region r while its
  reverse (v,u) lives in region q — exactly the paper's region network
  ``G^R`` in which incoming boundary arcs ``(B^R, R)`` have zero capacity
  *inside* R (they simply are not R's rows).

All capacities are int32 (the paper uses natural numbers); flow arithmetic is
exact.  ``INF_CAP`` marks "unbounded" arcs used by region reduction.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtypes as _dt
from repro.core.dtypes import KernelDtypes

# Large-but-safe sentinel values (int32 arithmetic must never overflow:
# INF_LABEL + 1 and INF_CAP + INF_CAP must stay < 2**31).  Narrowed
# storage (``dtype_policy="auto"|"narrow"``) swaps in the int16 sentinel
# ``dtypes.NARROW_INF_LABEL`` wherever labels are narrow.
INF_LABEL = np.int32(2**30)
INF_CAP = np.int32(2**30)


@dataclass(frozen=True)
class GraphMeta:
    """Static (host-side) metadata for a region-partitioned network."""

    num_regions: int          # K
    region_size: int          # V  (padded per-region vertex count)
    max_degree: int           # E  (padded per-vertex arc slots)
    num_vertices: int         # n  (true, unpadded)
    num_boundary: int         # |B|  (vertices incident to inter-region arcs)
    num_cross_arcs: int       # X  (directed inter-region arcs, padded table)
    num_ghost_groups: int     # distinct (region, adjacent-ghost) pairs
    d_inf_ard: int            # |B|      (ARD label ceiling, paper Sec. 4.1)
    d_inf_prd: int            # n        (PRD label ceiling, paper Sec. 2)
    # storage dtypes selected at build time (dtype_policy); recorded here
    # so every compile-cache key that hashes the meta stays sound when the
    # same shapes are built under a different narrowing policy
    label_dtype: str = "int32"
    flow_dtype: str = "int32"
    mask_dtype: str = "int32"

    def __post_init__(self):
        assert self.num_regions >= 1
        assert self.region_size >= 1

    @property
    def kernel_dtypes(self) -> KernelDtypes:
        return KernelDtypes(label=self.label_dtype, flow=self.flow_dtype,
                            mask=self.mask_dtype)


@jax.tree_util.register_dataclass
@dataclass
class FlowState:
    """Device-resident mutable state of the solver (a JAX pytree).

    Shapes: K = num_regions, V = region_size, E = max_degree,
    X = num_cross_arcs (flattened inter-region arc table).
    """

    # --- static topology (never mutated) ---
    nbr_region: jax.Array    # i32[K,V,E] neighbour's region id (== own for intra)
    nbr_local: jax.Array     # i32[K,V,E] neighbour's local vertex id
    rev_slot: jax.Array      # i32[K,V,E] slot of the reverse arc in nbr's row
    emask: jax.Array         # bool[K,V,E] valid arc slot
    vmask: jax.Array         # bool[K,V] valid vertex
    is_boundary: jax.Array   # bool[K,V] vertex in the boundary set B
    # flat cross-arc table: for cross arc x: (region,local,slot) of source row
    cross_src: jax.Array     # i32[X,3]
    cross_dst: jax.Array     # i32[X,3]  (row holding the reverse arc)
    cross_group: jax.Array   # i32[X]    id of the (src_region, dst_vertex)
    #                                    pair — "ghost w as seen from R"
    cross_valid: jax.Array   # bool[X]   padded-entry mask
    # flat scatter indices of the cross table, precomputed at build time so
    # no jitted sweep rebuilds them: arc index (r*V + l)*E + s into the
    # flattened [K,V,E] arrays, vertex index r*V + l into flattened [K,V]
    cross_src_arc: jax.Array  # i32[X]
    cross_dst_arc: jax.Array  # i32[X]
    cross_src_vtx: jax.Array  # i32[X]
    cross_dst_vtx: jax.Array  # i32[X]
    # --- mutable flow state ---
    cf: jax.Array            # i32[K,V,E] residual capacity of each arc
    sink_cf: jax.Array       # i32[K,V]  residual capacity of the t-link
    excess: jax.Array        # i32[K,V]  current excess e_f(v)
    d: jax.Array             # i32[K,V]  distance labels
    flow_to_t: jax.Array     # i32[]     |f| — total flow absorbed by the sink

    def active(self, d_inf: int) -> jax.Array:
        """Active vertices w.r.t. (f, d): positive excess and d < d_inf."""
        return (self.excess > 0) & (self.d < d_inf) & self.vmask

    def replace(self, **kw) -> "FlowState":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class Problem:
    """Host-side problem description before region blocking."""

    num_vertices: int
    edges: np.ndarray        # i64[m, 2]  undirected pairs (u, v), u != v
    cap_fwd: np.ndarray      # i32[m]     capacity u->v
    cap_bwd: np.ndarray      # i32[m]     capacity v->u
    excess: np.ndarray       # i32[n]     source-side terminal mass (paper Init)
    sink_cap: np.ndarray     # i32[n]     t-link capacity


def _check_problem(p: Problem) -> None:
    n, m = p.num_vertices, len(p.edges)
    assert p.edges.shape == (m, 2)
    assert p.cap_fwd.shape == (m,) and p.cap_bwd.shape == (m,)
    assert p.excess.shape == (n,) and p.sink_cap.shape == (n,)
    assert (p.cap_fwd >= 0).all() and (p.cap_bwd >= 0).all()
    assert (p.excess >= 0).all() and (p.sink_cap >= 0).all()
    if m:
        assert p.edges.min() >= 0 and p.edges.max() < n
        assert (p.edges[:, 0] != p.edges[:, 1]).all(), "self loops not allowed"


class ProblemValidationError(ValueError):
    """A ``Problem`` carries capacities the int32 solver cannot run safely.

    Raised by :func:`validate_problem` — the typed front door for
    negative/overflow-risk inputs; the bare ``_check_problem`` asserts
    stay as the internal (post-validation) sanity net inside ``build``.
    """


def validate_problem(p: Problem, *, context: str = "problem",
                     dtype_policy: str = "int32") -> None:
    """Reject negative and overflow-risk capacities before they reach the
    int32 flow arithmetic.

    The solver's sentinels (``INF_CAP = INF_LABEL = 2**30``) rely on int32
    sums never overflowing (see the module header): per undirected edge
    the two directed capacities share one residual budget
    (``cf(u,v) + cf(v,u)`` is invariant under pushes), per vertex
    ``excess + sink_cf`` rides the same bound, the total source mass
    bounds every accumulated excess and ``flow_to_t``, and the cut-cost
    certificate sums capacities across the cut.  Checks (all sums in
    int64):

    * shapes consistent, edge endpoints in range, no self loops;
    * every capacity/terminal >= 0;
    * per edge: ``cap_fwd + cap_bwd < INF_CAP``;
    * per vertex: ``excess + sink_cap < INF_CAP``;
    * ``sum(excess) < INF_CAP`` (bounds excess accumulation, flow_to_t);
    * ``sum(excess) + sum(sink_cap) + sum(caps) < 2**31`` (bounds the
      cut-cost certificate reduction).

    Under ``dtype_policy="narrow"`` (forced int16 storage) the bounds
    tighten: the total capacity mass must fit the narrowed residual dtype
    and the label ceiling the narrowed label dtype — a violation is a
    typed error naming the dtype and bound instead of silent wraparound.
    ``"auto"`` needs no extra checks here (it falls back to int32).

    Raises :class:`ProblemValidationError` (a ``ValueError``) naming the
    first offending quantity.  ``context`` labels the error source
    ("prepare", "update", a DIMACS path, ...).
    """
    n, m = p.num_vertices, len(p.edges)

    def fail(msg: str):
        raise ProblemValidationError(f"invalid {context}: {msg}")

    if p.edges.shape != (m, 2):
        fail(f"edges shape {p.edges.shape} != ({m}, 2)")
    if p.cap_fwd.shape != (m,) or p.cap_bwd.shape != (m,):
        fail(f"edge-capacity shapes {p.cap_fwd.shape}/{p.cap_bwd.shape} "
             f"!= ({m},)")
    if p.excess.shape != (n,) or p.sink_cap.shape != (n,):
        fail(f"terminal shapes {p.excess.shape}/{p.sink_cap.shape} != ({n},)")
    if m:
        if p.edges.min() < 0 or p.edges.max() >= n:
            fail("edge endpoint outside [0, num_vertices)")
        if (p.edges[:, 0] == p.edges[:, 1]).any():
            fail("self loop")
    for name, a in (("cap_fwd", p.cap_fwd), ("cap_bwd", p.cap_bwd),
                    ("excess", p.excess), ("sink_cap", p.sink_cap)):
        a = np.asarray(a)
        if a.size and int(a.min()) < 0:
            fail(f"negative {name} (min {int(a.min())}) at index "
                 f"{int(np.argmin(a))}")
    inf = int(INF_CAP)
    pair = p.cap_fwd.astype(np.int64) + p.cap_bwd.astype(np.int64)
    if m and int(pair.max()) >= inf:
        i = int(np.argmax(pair))
        fail(f"edge {i}: cap_fwd + cap_bwd = {int(pair[i])} >= INF_CAP "
             f"(2^30) — the shared residual budget of one edge overflows")
    term = p.excess.astype(np.int64) + p.sink_cap.astype(np.int64)
    if n and int(term.max()) >= inf:
        i = int(np.argmax(term))
        fail(f"vertex {i}: excess + sink_cap = {int(term[i])} >= INF_CAP "
             f"(2^30)")
    total_excess = int(p.excess.astype(np.int64).sum())
    if total_excess >= inf:
        fail(f"sum(excess) = {total_excess} >= INF_CAP (2^30) — "
             f"accumulated excess / flow_to_t can overflow int32")
    total = (total_excess + int(p.sink_cap.astype(np.int64).sum())
             + int(pair.sum()))
    if total >= 2**31:
        fail(f"total capacity mass {total} >= 2^31 — the int32 cut-cost "
             f"certificate reduction can overflow")
    # forced-narrow policy: the int16 families must actually fit.  The
    # label bound is the conservative problem-level one (n + 2 dominates
    # max(n, V + 2) for every partition, since V <= n).
    for family, dt, value, limit in _dt.narrow_violations(
            dtype_policy, mass=total, bound=n + 2):
        what = ("total capacity mass" if family == "flow"
                else "label ceiling")
        fail(f"{what} {value} exceeds the {dt} {family} bound {limit} "
             f"under dtype_policy='narrow' — narrowed {family} storage "
             f"would wrap; use dtype_policy='auto' (int32 fallback) or "
             f"'int32'")


def validate_update_dtypes(meta, p: Problem, *,
                           context: str = "update") -> None:
    """A capacity update on a handle built with narrowed storage must still
    fit the narrow ranges.

    The handle's dtypes are frozen at ``build`` time (they key the compile
    cache), so an update that pushes the total capacity mass past the int16
    bound cannot silently widen — and silently wrapping would corrupt flow.
    Typed error instead; the label bound depends only on the fixed topology
    and cannot change under an update.
    """
    kd = meta.kernel_dtypes
    if kd.flow != "int16":
        return
    mass = _dt.flow_mass(p)
    if not _dt.flows_fit_narrow(mass):
        raise ProblemValidationError(
            f"invalid {context}: total capacity mass {mass} exceeds the "
            f"int16 flow bound {_dt.NARROW_FLOW_LIMIT} of this prepared "
            f"handle's narrowed storage — re-prepare the problem (a fresh "
            f"build under dtype_policy='auto' falls back to int32)")


@dataclass(frozen=True)
class Layout:
    """Host-side mapping between flat vertex ids and (region, local) slots.

    ``edge_arc_u``/``edge_arc_v`` give, for every undirected input edge i,
    the flat ``[K*V*E]`` index of its two directed arc slots (u's row and
    v's row); ``edge_vtx_u``/``edge_vtx_v`` the flat ``[K*V]`` index of its
    endpoints.  They are what lets a prepared handle scatter a capacity
    delta straight onto the device-resident ``FlowState`` without
    re-running ``build`` (``apply_update``).
    """

    part: np.ndarray        # i64[n] region of each vertex
    local_id: np.ndarray    # i64[n] slot within the region
    edge_arc_u: np.ndarray | None = None   # i64[m] flat arc slot of u->v
    edge_arc_v: np.ndarray | None = None   # i64[m] flat arc slot of v->u
    edge_vtx_u: np.ndarray | None = None   # i64[m] flat vertex slot of u
    edge_vtx_v: np.ndarray | None = None   # i64[m] flat vertex slot of v

    def to_flat(self, arr_kv: np.ndarray) -> np.ndarray:
        """Gather a [K,V] per-slot array back to flat vertex order."""
        return np.asarray(arr_kv)[self.part, self.local_id]


def _stable_cumcount(keys: np.ndarray) -> np.ndarray:
    """Occurrence index of each element among equal values, in array order.

    Vectorized equivalent of ``count[k]; count[k] += 1`` loops: a stable
    argsort groups equal keys while preserving their original order, so the
    within-group offset is position minus group start.  ``build`` and the
    shard-wise streaming build (``repro.stream.build``) both derive arc
    slots from it, which is what makes their layouts bit-identical.
    """
    n = len(keys)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    starts = np.r_[0, np.flatnonzero(sk[1:] != sk[:-1]) + 1]
    counts = np.diff(np.r_[starts, n])
    out = np.empty(n, dtype=np.int64)
    out[order] = np.arange(n, dtype=np.int64) - np.repeat(starts, counts)
    return out


def build(problem: Problem, part: np.ndarray, *,
          dtype_policy: str = "int32") -> tuple[GraphMeta, FlowState, "Layout"]:
    """Block a flat problem into the region-partitioned device layout.

    ``part[v]`` gives the region id of vertex v (0..K-1).  Pure numpy; runs
    once on the host (the paper's ``splitter`` tool, Sec. 5.3).

    ``dtype_policy`` selects the storage dtypes of the mutable state
    (``repro.core.dtypes``): ``"auto"``/``"narrow"`` store residuals and
    excess as int16 when the total capacity mass fits and labels as int16
    when the label ceiling fits, recording the choice in ``GraphMeta`` so
    compile-cache keys stay sound; ``"auto"`` falls back to int32 per
    family, ``"narrow"`` raises ``ProblemValidationError`` instead.
    """
    _check_problem(problem)
    n = problem.num_vertices
    part = np.asarray(part, dtype=np.int64)
    assert part.shape == (n,)
    K = int(part.max()) + 1 if n else 1

    # local ids within each region (cumcount in vertex order, per region)
    local_id = _stable_cumcount(part)
    region_count = np.bincount(part, minlength=K)
    V = max(1, int(region_count.max()) if n else 0)

    # per-vertex directed arc lists (both directions of every undirected edge)
    u_arr = problem.edges[:, 0]
    v_arr = problem.edges[:, 1]
    deg = np.zeros(n, dtype=np.int64)
    np.add.at(deg, u_arr, 1)
    np.add.at(deg, v_arr, 1)
    E = max(1, int(deg.max()) if n else 1)

    nbr_region = np.full((K, V, E), 0, dtype=np.int32)
    nbr_local = np.full((K, V, E), 0, dtype=np.int32)
    rev_slot = np.zeros((K, V, E), dtype=np.int32)
    emask = np.zeros((K, V, E), dtype=bool)
    cf = np.zeros((K, V, E), dtype=np.int32)

    # first pass: assign slots — cumcount over the interleaved (u, v)
    # endpoint sequence, exactly the per-vertex counter a scalar loop
    # over edges would keep
    m = len(problem.edges)
    occ = np.empty(2 * m, dtype=np.int64)
    occ[0::2] = u_arr
    occ[1::2] = v_arr
    cc = _stable_cumcount(occ)
    slot_u, slot_v = cc[0::2], cc[1::2]
    # second pass: fill rows (vectorised where possible)
    ru, lu = part[u_arr], local_id[u_arr]
    rv, lv = part[v_arr], local_id[v_arr]
    nbr_region[ru, lu, slot_u] = rv.astype(np.int32)
    nbr_local[ru, lu, slot_u] = lv.astype(np.int32)
    rev_slot[ru, lu, slot_u] = slot_v.astype(np.int32)
    emask[ru, lu, slot_u] = True
    cf[ru, lu, slot_u] = problem.cap_fwd
    nbr_region[rv, lv, slot_v] = ru.astype(np.int32)
    nbr_local[rv, lv, slot_v] = lu.astype(np.int32)
    rev_slot[rv, lv, slot_v] = slot_u.astype(np.int32)
    emask[rv, lv, slot_v] = True
    cf[rv, lv, slot_v] = problem.cap_bwd

    vmask = np.zeros((K, V), dtype=bool)
    vmask[part, local_id] = True

    sink_cf = np.zeros((K, V), dtype=np.int32)
    sink_cf[part, local_id] = problem.sink_cap
    excess = np.zeros((K, V), dtype=np.int32)
    excess[part, local_id] = problem.excess

    # boundary set B: endpoints of inter-region edges
    cross_edge = ru != rv
    is_boundary = np.zeros((K, V), dtype=bool)
    if cross_edge.any():
        cu = u_arr[cross_edge]; cv = v_arr[cross_edge]
        is_boundary[part[cu], local_id[cu]] = True
        is_boundary[part[cv], local_id[cv]] = True
    num_boundary = int(is_boundary.sum())

    # flat directed cross-arc table.  Invariant: arcs come in mutual-reverse
    # pairs at indices (2i, 2i+1), so pair(x) = x ^ 1.
    src_list, dst_list = [], []
    idx = np.nonzero(cross_edge)[0]
    for i in idx:
        u, v = u_arr[i], v_arr[i]
        a = (part[u], local_id[u], slot_u[i])
        b = (part[v], local_id[v], slot_v[i])
        src_list += [a, b]
        dst_list += [b, a]
    X = max(1, len(src_list))
    cross_src = np.zeros((X, 3), dtype=np.int32)
    cross_dst = np.zeros((X, 3), dtype=np.int32)
    cross_group = np.zeros(X, dtype=np.int32)
    cross_valid = np.zeros(X, dtype=bool)
    num_groups = 1
    if src_list:
        cross_src[: len(src_list)] = np.asarray(src_list, dtype=np.int32)
        cross_dst[: len(dst_list)] = np.asarray(dst_list, dtype=np.int32)
        cross_valid[: len(src_list)] = True
        # group id of the (viewing region, ghost vertex) pair: arcs from R to
        # the same boundary vertex w share a group — region reduction and the
        # boundary heuristics aggregate per ghost, not per arc.
        keys = {}
        for x in range(len(src_list)):
            k = (src_list[x][0], dst_list[x][0], dst_list[x][1])
            cross_group[x] = keys.setdefault(k, len(keys))
        num_groups = max(1, len(keys))

    kd = _dt.select_dtypes(dtype_policy, mass=_dt.flow_mass(problem),
                           bound=_dt.label_bound(n, V))
    bad = _dt.narrow_violations(dtype_policy, mass=_dt.flow_mass(problem),
                                bound=_dt.label_bound(n, V))
    if bad:
        family, dt, value, limit = bad[0]
        raise ProblemValidationError(
            f"invalid build: {family} range {value} exceeds the {dt} "
            f"bound {limit} under dtype_policy='narrow'")

    meta = GraphMeta(
        num_regions=K,
        region_size=V,
        max_degree=E,
        num_vertices=n,
        num_boundary=num_boundary,
        num_cross_arcs=X,
        num_ghost_groups=num_groups,
        d_inf_ard=max(1, num_boundary),
        d_inf_prd=max(1, n),
        label_dtype=kd.label,
        flow_dtype=kd.flow,
        mask_dtype=kd.mask,
    )
    state = FlowState(
        nbr_region=jnp.asarray(nbr_region),
        nbr_local=jnp.asarray(nbr_local),
        rev_slot=jnp.asarray(rev_slot),
        emask=jnp.asarray(emask),
        vmask=jnp.asarray(vmask),
        is_boundary=jnp.asarray(is_boundary),
        cross_src=jnp.asarray(cross_src),
        cross_dst=jnp.asarray(cross_dst),
        cross_group=jnp.asarray(cross_group),
        cross_valid=jnp.asarray(cross_valid),
        cross_src_arc=jnp.asarray(
            (cross_src[:, 0].astype(np.int64) * V + cross_src[:, 1]) * E
            + cross_src[:, 2], dtype=jnp.int32),
        cross_dst_arc=jnp.asarray(
            (cross_dst[:, 0].astype(np.int64) * V + cross_dst[:, 1]) * E
            + cross_dst[:, 2], dtype=jnp.int32),
        cross_src_vtx=jnp.asarray(
            cross_src[:, 0].astype(np.int64) * V + cross_src[:, 1],
            dtype=jnp.int32),
        cross_dst_vtx=jnp.asarray(
            cross_dst[:, 0].astype(np.int64) * V + cross_dst[:, 1],
            dtype=jnp.int32),
        cf=jnp.asarray(cf.astype(kd.flow_np)),
        sink_cf=jnp.asarray(sink_cf.astype(kd.flow_np)),
        excess=jnp.asarray(excess.astype(kd.flow_np)),
        d=jnp.zeros((K, V), dtype=kd.label_np),
        flow_to_t=jnp.zeros((), dtype=jnp.int32),
    )
    layout = Layout(
        part=part, local_id=local_id,
        edge_arc_u=(ru * V + lu) * E + slot_u,
        edge_arc_v=(rv * V + lv) * E + slot_v,
        edge_vtx_u=ru * V + lu,
        edge_vtx_v=rv * V + lv)
    return meta, state, layout


def init_labels(meta: GraphMeta, state: FlowState) -> FlowState:
    """Paper's ``Init``: d := 0 everywhere (source already eliminated)."""
    return state.replace(d=jnp.zeros_like(state.d))


# --------------------------------------------------------------------------
# Per-region state slabs: the streaming executor's unit of disk I/O.  One
# region's view is [V,E]/[V] arrays — never the full [K,V,E] state — split
# into the immutable topology (spilled once per solve) and the mutable flow
# family (staged in/out every region visit).
# --------------------------------------------------------------------------

REGION_TOPO_FIELDS = ("nbr_region", "nbr_local", "rev_slot", "emask",
                      "vmask", "is_boundary")
REGION_FLOW_FIELDS = ("cf", "sink_cf", "excess", "d")


def extract_region(state: FlowState, r: int, fields=None) -> dict:
    """One region's slabs as host numpy arrays: ``{field: array[V,E]|[V]}``.

    ``fields`` defaults to topology + flow; pass ``REGION_FLOW_FIELDS`` /
    ``REGION_TOPO_FIELDS`` to stage one family.  Fetches only the indexed
    slices — a prepared handle spilling its regions to disk never copies
    the whole state to host at once.
    """
    if fields is None:
        fields = REGION_TOPO_FIELDS + REGION_FLOW_FIELDS
    return {f: np.asarray(getattr(state, f)[r]) for f in fields}


def insert_region(state: FlowState, r: int, shard: dict) -> FlowState:
    """Write one region's mutable slabs back into a full ``FlowState``.

    The inverse of :func:`extract_region` over the flow family (topology is
    immutable and never re-inserted); used to reassemble a resident state
    from streamed shards for cut extraction / certificate checks.
    """
    upd = {}
    for f in REGION_FLOW_FIELDS:
        if f in shard:
            cur = getattr(state, f)
            upd[f] = cur.at[r].set(jnp.asarray(shard[f], dtype=cur.dtype))
    return state.replace(**upd)


# --------------------------------------------------------------------------
# Warm-start updates: reparameterize the residual network under a capacity
# delta (Kohli-Torr dynamic-cuts style), keeping the preflow device-resident.
# --------------------------------------------------------------------------

# traces of the jitted update program — a session's ``cache_info`` counts
# these together with the sweep/batch program traces
_UPDATE_TRACES = 0


def update_trace_count() -> int:
    return _UPDATE_TRACES


@jax.tree_util.register_dataclass
@dataclass
class GraphUpdate:
    """Device-side capacity/terminal delta of a prepared problem (a pytree).

    ``j`` edge entries and ``p`` vertex entries, each padded (to a power of
    two by the session front-end) with index-0 / zero-delta slots that are
    inert under the scatter arithmetic of ``apply_update`` — so repeated
    same-sized updates reuse one compiled program.  Indices are flat:
    ``arc_*`` into the flattened ``[K*V*E]`` residual table (the build-time
    ``Layout.edge_arc_*`` slots of the updated edges), ``vtx_*``/``t_vtx``
    into the flattened ``[K*V]`` vertex arrays.
    """

    arc_u: jax.Array       # i32[j] flat slot of the edge's u->v arc
    arc_v: jax.Array       # i32[j] flat slot of the edge's v->u arc
    vtx_u: jax.Array       # i32[j] flat vertex slot of u
    vtx_v: jax.Array       # i32[j] flat vertex slot of v
    d_cap_fwd: jax.Array   # i32[j] capacity delta of u->v
    d_cap_bwd: jax.Array   # i32[j] capacity delta of v->u
    t_vtx: jax.Array       # i32[p] flat vertex slot of a terminal update
    d_sink: jax.Array      # i32[p] t-link capacity delta
    d_excess: jax.Array    # i32[p] source-mass delta


@jax.jit
def apply_update(state: FlowState, state0: FlowState, upd: GraphUpdate):
    """Apply a capacity/terminal delta to a solved (or fresh) ``FlowState``.

    The residual network is reparameterized in the Kohli-Torr dynamic-cuts
    style so the current preflow stays valid on the updated problem:

    * each updated edge's residual pair moves by the capacity delta; where
      the new capacity falls below the flow the residual is clamped to 0
      and the clamped overflow is *returned to the sender's excess*, with
      the matching inflow deficit charged to the receiver;
    * t-link decreases below the flow already drained return the overflow
      to the vertex excess and roll ``flow_to_t`` back;
    * a deficit a vertex cannot cover from its (post-return) excess is
      cancelled by adding the shortfall to BOTH its conceptual source arc
      (absorbed into excess, netting zero) and its t-link ``sink_cf`` —
      adding the same amount to (s,v) and (v,t) raises every s-t cut by
      exactly that constant, so the mincut partition is unchanged and the
      solved flow value is simply ``flow_to_t - offset``.

    Returns ``(state', state0', grew, offset_delta)`` where ``state0'`` is
    the *unreparameterized* initial network of the updated problem (what
    cut-cost checks price cuts against), ``grew`` flags whether any
    residual capacity increased (new residual arcs can invalidate kept
    labels — see ``SolverOptions.warm_labels``), and ``offset_delta`` is
    the flow-value offset introduced by deficit cancellation.
    """
    global _UPDATE_TRACES
    _UPDATE_TRACES += 1
    K, V, E = state.cf.shape

    # --- edge capacity deltas, clamped into the new capacity ---
    # deltas arrive int32; the state may be stored narrow — cast at the
    # door (the session front-end re-validates that the updated problem
    # still fits the narrowed ranges, so the casts cannot wrap)
    cf = state.cf.reshape(-1)
    fdt = cf.dtype
    d_fwd = upd.d_cap_fwd.astype(fdt)
    d_bwd = upd.d_cap_bwd.astype(fdt)
    d_sink_t = upd.d_sink.astype(fdt)
    d_excess_t = upd.d_excess.astype(fdt)
    ra0, rb0 = cf[upd.arc_u], cf[upd.arc_v]
    ra = ra0 + d_fwd
    rb = rb0 + d_bwd
    # at most one side of a pair can go negative (ra + rb = c_f' + c_b' >= 0)
    ov_a = jnp.maximum(-ra, 0)          # flow over the new u->v capacity
    ra, rb = ra + ov_a, rb - ov_a
    ov_b = jnp.maximum(-rb, 0)          # flow over the new v->u capacity
    rb, ra = rb + ov_b, ra - ov_b
    cf = cf.at[upd.arc_u].add(ra - ra0, mode="drop")
    cf = cf.at[upd.arc_v].add(rb - rb0, mode="drop")

    # clamped overflow goes back to the sender; the receiver is charged
    nv = K * V
    returns = jnp.zeros((nv,), fdt).at[upd.vtx_u].add(ov_a, mode="drop")
    returns = returns.at[upd.vtx_v].add(ov_b, mode="drop")
    deficits = jnp.zeros((nv,), fdt).at[upd.vtx_v].add(ov_a, mode="drop")
    deficits = deficits.at[upd.vtx_u].add(ov_b, mode="drop")

    # --- terminal deltas ---
    sink = state.sink_cf.reshape(-1)
    s0 = sink[upd.t_vtx]
    s1 = s0 + d_sink_t
    t_ret = jnp.maximum(-s1, 0)         # flow returned from the sink
    s1 = s1 + t_ret
    sink = sink.at[upd.t_vtx].add(s1 - s0, mode="drop")
    flow_to_t = state.flow_to_t - jnp.sum(t_ret, dtype=jnp.int32)
    returns = returns.at[upd.t_vtx].add(
        t_ret + jnp.maximum(d_excess_t, 0), mode="drop")
    deficits = deficits.at[upd.t_vtx].add(
        jnp.maximum(-d_excess_t, 0), mode="drop")

    # --- resolve deficits against excess; cancel the shortfall ---
    excess = state.excess.reshape(-1) + returns
    short = jnp.maximum(deficits - excess, 0)
    excess = jnp.maximum(excess - deficits, 0)
    sink = sink + short
    offset = jnp.sum(short, dtype=jnp.int32)

    grew = ((ra > ra0).any() | (rb > rb0).any() | (s1 > s0).any()
            | (short > 0).any())

    new_state = state.replace(
        cf=cf.reshape(K, V, E), sink_cf=sink.reshape(K, V),
        excess=excess.reshape(K, V), flow_to_t=flow_to_t)

    # initial network of the updated problem (zero flow): plain deltas
    cf0 = state0.cf.reshape(-1).at[upd.arc_u].add(d_fwd, mode="drop")
    cf0 = cf0.at[upd.arc_v].add(d_bwd, mode="drop")
    sink0 = state0.sink_cf.reshape(-1).at[upd.t_vtx].add(d_sink_t,
                                                         mode="drop")
    exc0 = state0.excess.reshape(-1).at[upd.t_vtx].add(d_excess_t,
                                                       mode="drop")
    new_state0 = state0.replace(
        cf=cf0.reshape(K, V, E), sink_cf=sink0.reshape(K, V),
        excess=exc0.reshape(K, V))
    return new_state, new_state0, grew, offset


# --------------------------------------------------------------------------
# Multi-instance packing: stack independent problems into shape buckets.
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class BatchMeta:
    """Static bucket-shape metadata of a packed instance batch.

    Deliberately holds ONLY the padded bucket dimensions — everything that
    varies between same-shaped batches (instance count, label ceilings,
    sweep bounds) lives in ``BatchState`` device arrays or host-side in
    ``PackedBatch``, so a compiled batched solve is keyed purely by
    ``(bucket_shape, SweepConfig)`` and is reused verbatim for any batch
    that lands in the same bucket.
    """

    num_instances: int        # B  (padded bucket batch size)
    num_regions: int          # K  (padded)
    region_size: int          # V  (padded)
    max_degree: int           # E  (padded)
    num_cross_arcs: int       # X  (padded)
    # storage dtypes of the bucket (all members share them — packing
    # groups by dtype as well as shape); part of the compile-cache key
    label_dtype: str = "int32"
    flow_dtype: str = "int32"
    mask_dtype: str = "int32"

    @property
    def bucket_shape(self) -> tuple[int, int, int, int, int]:
        return (self.num_instances, self.num_regions, self.region_size,
                self.max_degree, self.num_cross_arcs)

    @property
    def kernel_dtypes(self) -> KernelDtypes:
        return KernelDtypes(label=self.label_dtype, flow=self.flow_dtype,
                            mask=self.mask_dtype)


@jax.tree_util.register_dataclass
@dataclass
class BatchState:
    """Device-resident state of a packed solve batch (a JAX pytree).

    The ``[B, ...]`` forms of the ``FlowState`` fields the batched sweep
    driver needs, plus the per-instance dynamic metadata (label ceilings)
    that a single solve bakes in statically from ``GraphMeta``.  Keeping
    the ceilings as device arrays is what lets instances of *different
    original sizes* share one bucket-shaped executable while running
    exactly the iteration sequence of their standalone solves.
    """

    # --- static topology (never mutated) ---
    nbr_region: jax.Array     # i32[B,K,V,E]
    nbr_local: jax.Array      # i32[B,K,V,E]
    rev_slot: jax.Array       # i32[B,K,V,E]
    emask: jax.Array          # bool[B,K,V,E]
    vmask: jax.Array          # bool[B,K,V]
    is_boundary: jax.Array    # bool[B,K,V]
    # flat cross-arc scatter/gather indices, recomputed for the bucket dims
    cross_src_arc: jax.Array  # i32[B,X]  (r*V + l)*E + s of the source row
    cross_dst_arc: jax.Array  # i32[B,X]
    cross_src_vtx: jax.Array  # i32[B,X]  r*V + l
    cross_dst_vtx: jax.Array  # i32[B,X]
    cross_valid: jax.Array    # bool[B,X] padded-entry mask
    # --- per-instance dynamic metadata ---
    d_inf_ard: jax.Array      # i32[B]  |B_b|  (ARD ceiling of instance b)
    d_inf_prd: jax.Array      # i32[B]  n_b    (PRD ceiling)
    linf: jax.Array           # i32[B]  V_b+2  (ARD stage/BFS local ceiling,
    #                                   the instance's ORIGINAL region size)
    # --- mutable flow state ---
    cf: jax.Array             # i32[B,K,V,E]
    sink_cf: jax.Array        # i32[B,K,V]
    excess: jax.Array         # i32[B,K,V]
    d: jax.Array              # i32[B,K,V]
    flow_to_t: jax.Array      # i32[B]

    def replace(self, **kw) -> "BatchState":
        return dataclasses.replace(self, **kw)


@dataclass
class PackedBatch:
    """Host-side handle on one shape bucket of a packed batch.

    ``metas``/``layouts``/``states0`` are the per-real-instance build
    artifacts (unpadded), kept for unpacking results, the cut check and
    the byte accounting; ``indices`` maps bucket slots back to positions
    in the caller's problem list.  Slots beyond ``len(indices)`` are inert
    padding instances (all-masked, zero excess) that converge at entry.
    """

    meta: BatchMeta
    state: BatchState
    metas: list
    layouts: list
    states0: list
    indices: list

    @property
    def num_real(self) -> int:
        return len(self.indices)


def _round_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def bucket_shape_for(meta: GraphMeta) -> tuple[int, int, int, int]:
    """(K, V, E, X) bucket of an instance: each dim rounded up to a power
    of two, so mixed problem sizes collapse onto a small set of compiled
    executables."""
    return (_round_pow2(meta.num_regions), _round_pow2(meta.region_size),
            _round_pow2(meta.max_degree), _round_pow2(meta.num_cross_arcs))


def _pad_to(a: np.ndarray, shape: tuple[int, ...]) -> np.ndarray:
    return np.pad(a, [(0, s - d) for d, s in zip(a.shape, shape)])


def pack_instances(problems, parts=None, *, num_regions: int = 4,
                   pad_batch: bool = True,
                   dtype_policy: str = "int32") -> list[PackedBatch]:
    """Stack independent problems into shape-bucketed solve batches.

    Each problem is region-blocked with ``build`` (``parts[i]`` or the
    node-number fallback partitioner) and handed to ``pack_built`` — one
    ``PackedBatch`` per power-of-two shape bucket.  ``dtype_policy`` runs
    the per-problem capacity/label range check of ``build``; instances
    resolving to different storage dtypes land in different buckets.
    """
    from repro.core.partition import block_partition

    builds = []
    for i, p in enumerate(problems):
        part = parts[i] if parts is not None and parts[i] is not None \
            else block_partition(p.num_vertices, num_regions)
        meta, state, layout = build(p, np.asarray(part),
                                    dtype_policy=dtype_policy)
        builds.append((i, meta, state, layout, state))
    return pack_built(builds, pad_batch=pad_batch)


def pack_built(builds, *, pad_batch: bool = True) -> list[PackedBatch]:
    """Stack already-built instances into shape-bucketed solve batches.

    ``builds`` — ``(index, meta, state, layout, state0)`` tuples: ``state``
    is the FlowState the batched solve starts from (fresh from ``build``,
    or a session handle's warm, possibly-updated state — its preflow,
    labels and ``flow_to_t`` are all carried into the batch), ``state0``
    the instance's initial network kept for result unpacking and the
    cut-cost check.  Each instance's (K, V, E, X) is rounded up to the
    power-of-two bucket and instances sharing a bucket are stacked along a
    new leading instance axis.  Padding is inert by construction:
    masked-off vertices/arcs/cross entries and (with ``pad_batch``) the
    batch axis rounded up with all-masked dummy instances, so any batch
    landing in a bucket reuses the bucket's compiled solve.  Returns one
    ``PackedBatch`` per bucket (ascending bucket shape).
    """
    groups: dict = {}
    for item in builds:
        m = item[1]
        key = bucket_shape_for(m) + (m.label_dtype, m.flow_dtype,
                                     m.mask_dtype)
        groups.setdefault(key, []).append(item)

    out = []
    for (K, V, E, X, label_dt, flow_dt, mask_dt), items \
            in sorted(groups.items()):
        B = _round_pow2(len(items)) if pad_batch else len(items)
        fdt, ldt = np.dtype(flow_dt), np.dtype(label_dt)
        shp3 = {"nbr_region": np.int32, "nbr_local": np.int32,
                "rev_slot": np.int32, "emask": bool, "cf": fdt}
        shp2 = {"vmask": bool, "is_boundary": bool, "sink_cf": fdt,
                "excess": fdt, "d": ldt}
        cols = {k: np.zeros((B, K, V, E), dt) for k, dt in shp3.items()}
        cols.update({k: np.zeros((B, K, V), dt) for k, dt in shp2.items()})
        cross = {k: np.zeros((B, X), np.int32) for k in
                 ("cross_src_arc", "cross_dst_arc",
                  "cross_src_vtx", "cross_dst_vtx")}
        cross_valid = np.zeros((B, X), bool)
        d_inf_ard = np.ones(B, np.int32)
        d_inf_prd = np.ones(B, np.int32)
        linf = np.full(B, 3, np.int32)
        flow_to_t = np.zeros(B, np.int32)
        for b, (i, meta, state, layout, _state0) in enumerate(items):
            for k in shp3:
                cols[k][b] = _pad_to(np.asarray(getattr(state, k)), (K, V, E))
            for k in shp2:
                cols[k][b] = _pad_to(np.asarray(getattr(state, k)), (K, V))
            # flat scatter indices must be recomputed for the BUCKET dims —
            # the per-instance build derived them from its original (V, E)
            src = np.asarray(state.cross_src, np.int64)
            dst = np.asarray(state.cross_dst, np.int64)
            valid = np.asarray(state.cross_valid)
            n_x = len(valid)
            arc = lambda t: ((t[:, 0] * V + t[:, 1]) * E + t[:, 2]) \
                .astype(np.int32)
            vtx = lambda t: (t[:, 0] * V + t[:, 1]).astype(np.int32)
            cross["cross_src_arc"][b, :n_x] = arc(src)
            cross["cross_dst_arc"][b, :n_x] = arc(dst)
            cross["cross_src_vtx"][b, :n_x] = vtx(src)
            cross["cross_dst_vtx"][b, :n_x] = vtx(dst)
            cross_valid[b, :n_x] = valid
            d_inf_ard[b] = meta.d_inf_ard
            d_inf_prd[b] = meta.d_inf_prd
            linf[b] = meta.region_size + 2
            flow_to_t[b] = int(state.flow_to_t)
        state = BatchState(
            nbr_region=jnp.asarray(cols["nbr_region"]),
            nbr_local=jnp.asarray(cols["nbr_local"]),
            rev_slot=jnp.asarray(cols["rev_slot"]),
            emask=jnp.asarray(cols["emask"]),
            vmask=jnp.asarray(cols["vmask"]),
            is_boundary=jnp.asarray(cols["is_boundary"]),
            cross_src_arc=jnp.asarray(cross["cross_src_arc"]),
            cross_dst_arc=jnp.asarray(cross["cross_dst_arc"]),
            cross_src_vtx=jnp.asarray(cross["cross_src_vtx"]),
            cross_dst_vtx=jnp.asarray(cross["cross_dst_vtx"]),
            cross_valid=jnp.asarray(cross_valid),
            d_inf_ard=jnp.asarray(d_inf_ard),
            d_inf_prd=jnp.asarray(d_inf_prd),
            linf=jnp.asarray(linf),
            cf=jnp.asarray(cols["cf"]),
            sink_cf=jnp.asarray(cols["sink_cf"]),
            excess=jnp.asarray(cols["excess"]),
            d=jnp.asarray(cols["d"]),
            flow_to_t=jnp.asarray(flow_to_t),
        )
        out.append(PackedBatch(
            meta=BatchMeta(num_instances=B, num_regions=K, region_size=V,
                           max_degree=E, num_cross_arcs=X,
                           label_dtype=label_dt, flow_dtype=flow_dt,
                           mask_dtype=mask_dt),
            state=state,
            metas=[it[1] for it in items],
            layouts=[it[3] for it in items],
            states0=[it[4] for it in items],
            indices=[it[0] for it in items]))
    return out


def gather_at_nbr(values: jax.Array, nbr_region: jax.Array,
                  nbr_local: jax.Array) -> jax.Array:
    """``values[nbr_region, nbr_local]``: a per-vertex ``[K, V]`` array read
    at every arc's destination, shaped ``[K, V, E]``.

    The output takes the index arrays' sharding.  On region-sharded state
    (the explicit-axis mesh ``jax.make_mesh`` builds) the gather then
    all-gathers the small ``[K, V]`` operand; on unsharded state it is the
    plain gather.
    """
    return values.at[nbr_region, nbr_local].get(
        out_sharding=jax.typeof(nbr_region).sharding)


def intra_mask(state: FlowState) -> jax.Array:
    """bool[K,V,E] — arc stays within its own region."""
    K = state.nbr_region.shape[0]
    own = jnp.arange(K, dtype=state.nbr_region.dtype)[:, None, None]
    return (state.nbr_region == own) & state.emask


def flow_value(state: FlowState) -> jax.Array:
    return state.flow_to_t


def total_excess(state: FlowState) -> jax.Array:
    return jnp.sum(jnp.where(state.vmask, state.excess, 0),
                   dtype=jnp.int32)
