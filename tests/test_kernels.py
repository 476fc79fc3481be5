"""Pallas kernels vs pure-jnp oracles across shape/dtype sweeps
(interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import push_relabel, push_relabel_batched
from repro.kernels.flash_attention import flash_attention
from repro.kernels.push_relabel import (engine_phase, fused_engine_run,
                                        fused_engine_run_batched,
                                        push_relabel_phase)
from repro.kernels.ref import (attention_ref, fused_iteration_ref,
                               push_relabel_iteration_ref)

ATTN_SHAPES = [
    # B, H, Hkv, Sq, Sk, D
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 128, 128, 64),
    (1, 4, 1, 96, 96, 32),      # MQA
    (1, 2, 1, 1, 128, 32),      # decode: one query against a cache
    (1, 1, 1, 37, 53, 16),      # ragged (padding path)
    (1, 2, 2, 200, 200, 128),   # head_dim 128 (lane width)
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", ATTN_SHAPES,
                         ids=[str(s) for s in ATTN_SHAPES])
def test_flash_attention_matches_ref(shape, dtype):
    B, H, Hkv, Sq, Sk, D = shape
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, Sq, D), dtype)
    k = jnp.asarray(rng.randn(B, Hkv, Sk, D), dtype)
    v = jnp.asarray(rng.randn(B, Hkv, Sk, D), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=64,
                          interpret=True)
    kk = jnp.repeat(k, H // Hkv, 1)
    vv = jnp.repeat(v, H // Hkv, 1)
    want = attention_ref(q, kk, vv, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (64, 128)])
def test_flash_attention_block_shape_independence(block_q, block_k):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    a = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                        interpret=True)
    b = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


PR_SHAPES = [(16, 4), (33, 5), (64, 8), (128, 3)]


@pytest.mark.parametrize("V,E", PR_SHAPES, ids=[str(s) for s in PR_SHAPES])
@pytest.mark.parametrize("block_v", [8, 32])
def test_push_relabel_phase_matches_ref(V, E, block_v):
    rng = np.random.RandomState(V + E)
    cf = jnp.asarray(rng.randint(0, 50, (V, E)), jnp.int32)
    nbr = jnp.asarray(rng.randint(0, V, (V, E)), jnp.int32)
    intra = jnp.asarray((rng.rand(V, E) < 0.8), jnp.int32)
    pushable = jnp.ones((V, E), jnp.int32)
    cross_lab = jnp.asarray(rng.randint(0, 6, (V, E)), jnp.int32)
    lab = jnp.asarray(rng.randint(0, 8, (V,)), jnp.int32)
    excess = jnp.asarray(rng.randint(0, 40, (V,)), jnp.int32)
    sink_cf = jnp.asarray(rng.randint(0, 20, (V,)), jnp.int32)
    d_inf = 64
    got_d, got_l = push_relabel_phase(
        lab, cf, sink_cf, excess, nbr, intra, pushable, cross_lab, d_inf,
        block_v=block_v, interpret=True)
    want_d, want_l = push_relabel_iteration_ref(
        cf, sink_cf, excess, lab, nbr, None, intra != 0,
        jnp.ones((V, E), bool), jnp.ones((V,), bool), cross_lab,
        pushable != 0, d_inf)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))


def _random_region(V, E, seed):
    """Random region network: capacities, labels, masks and cross arcs are
    random, and its arcs pair up as a residual network's do.  The ``V*E``
    slots are cut into pairs by a shuffled perfect matching; each pair's
    slots name each other as ``(nbr_local, rev_slot)`` and share one
    ``intra`` draw, so ``rev_slot`` is an involution on intra arcs and
    ``intra`` is symmetric under it (the layout contract the engine's
    push apply relies on).  With ``V*E`` odd the slot left over is a
    cross arc.  The labeling need not be valid, so runs take a cap."""
    rng = np.random.RandomState(seed)
    cf = rng.randint(0, 50, (V, E))
    sink_cf = rng.randint(0, 20, (V,))
    excess = rng.randint(0, 40, (V,))
    lab = rng.randint(0, 8, (V,))
    nbr_local = rng.randint(0, V, V * E)
    rev_slot = rng.randint(0, E, V * E)
    intra = np.zeros(V * E, bool)
    pairs = rng.permutation(V * E)[:V * E // 2 * 2].reshape(-1, 2)
    for a, b in (pairs[:, 0], pairs[:, 1]), (pairs[:, 1], pairs[:, 0]):
        nbr_local[a], rev_slot[a] = b // E, b % E
    intra[pairs] = (rng.rand(len(pairs)) < 0.8)[:, None]
    return dict(
        cf=jnp.asarray(cf, jnp.int32),
        sink_cf=jnp.asarray(sink_cf, jnp.int32),
        excess=jnp.asarray(excess, jnp.int32),
        lab=jnp.asarray(lab, jnp.int32),
        nbr_local=jnp.asarray(nbr_local.reshape(V, E), jnp.int32),
        rev_slot=jnp.asarray(rev_slot.reshape(V, E), jnp.int32),
        intra=jnp.asarray(intra.reshape(V, E)),
        emask=jnp.asarray(rng.rand(V, E) < 0.9),
        vmask=jnp.asarray(rng.rand(V) < 0.95),
        cross_pushable=jnp.asarray(rng.rand(V, E) < 0.5),
        cross_lab=jnp.asarray(rng.randint(0, 6, (V, E)), jnp.int32),
    )


@pytest.mark.parametrize("V,E", PR_SHAPES, ids=[str(s) for s in PR_SHAPES])
@pytest.mark.parametrize("sink_open", [True, False])
def test_engine_phase_matches_xla_phase(V, E, sink_open):
    """kernels.engine_phase (pallas adapter) == engine._phase_xla, bit-exact,
    under the engine's cross_pushable/emask/vmask/sink_open gating."""
    from repro.core.engine import make_phase

    r = _random_region(V, E, seed=3 * V + E)
    kw = dict(nbr_local=r["nbr_local"], intra=r["intra"], emask=r["emask"],
              vmask=r["vmask"], cross_pushable=r["cross_pushable"],
              cross_lab=r["cross_lab"], d_inf=V + 2, sink_open=sink_open)
    want = make_phase("xla", **kw)(r["lab"], r["cf"], r["sink_cf"],
                                   r["excess"])
    got = engine_phase(r["lab"], r["cf"], r["sink_cf"], r["excess"],
                       block_v=8, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("V,E", PR_SHAPES, ids=[str(s) for s in PR_SHAPES])
def test_engine_backend_parity(V, E):
    """Full engine runs (while_loop of push+apply+relabel) are bit-identical
    between the XLA and Pallas compute-phase backends."""
    r = _random_region(V, E, seed=7 * V + E)
    kw = dict(nbr_local=r["nbr_local"], rev_slot=r["rev_slot"],
              intra=r["intra"], emask=r["emask"], vmask=r["vmask"],
              cross_pushable=r["cross_pushable"], cross_lab=r["cross_lab"],
              d_inf=V + 2, sink_open=True, max_iters=40)
    a = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend="xla", **kw)
    b = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend="pallas", block_v=8, **kw)
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"field {name}")


def _engine_kwargs(r, V, **over):
    kw = dict(nbr_local=r["nbr_local"], rev_slot=r["rev_slot"],
              intra=r["intra"], emask=r["emask"], vmask=r["vmask"],
              cross_pushable=r["cross_pushable"], cross_lab=r["cross_lab"],
              d_inf=V + 2, sink_open=True)
    kw.update(over)
    return kw


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("V,E", [(16, 4), (33, 5)],
                         ids=["(16,4)", "(33,5)"])
def test_fused_iteration_matches_ref_oracle(V, E, backend):
    """One fused engine iteration (push + intra apply + post-push relabel)
    is bit-equal to the kernels/ref.py fused-iteration oracle."""
    r = _random_region(V, E, seed=11 * V + E)
    es = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                      backend=backend, chunk_iters=1, max_iters=1,
                      **_engine_kwargs(r, V))
    want = fused_iteration_ref(
        r["cf"], r["sink_cf"], r["excess"], r["lab"], r["nbr_local"],
        r["rev_slot"], r["intra"], r["emask"], r["vmask"], r["cross_lab"],
        r["cross_pushable"], V + 2)
    got = (es.cf, es.sink_cf, es.excess, es.lab, es.out_push,
           es.sink_pushed, es.relabel_sum)
    names = ("cf", "sink_cf", "excess", "lab", "out_push", "sink_pushed",
             "relabel_sum")
    for name, x, y in zip(names, got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"field {name}")
    assert int(es.iters) == 1


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("V,E", PR_SHAPES, ids=[str(s) for s in PR_SHAPES])
def test_fused_engine_matches_unfused(V, E, backend, chunk):
    """The chunked fused driver (k iterations per launch) is bit-identical
    to the unfused two-phase engine — every state field including iteration
    counts — on both backends.  max_iters=16 is a whole number of chunks
    at chunk=8 (the mid-chunk early exit is covered on a consistent network
    by test_fused_early_exit_convergence; random regions need an iteration
    cap because their labeling can be permanently invalid)."""
    r = _random_region(V, E, seed=7 * V + E)
    kw = _engine_kwargs(r, V, max_iters=16)
    a = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend="xla", **kw)
    b = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend=backend, chunk_iters=chunk, **kw)
    for name, x, y in zip(a._fields, a, b):
        if name == "launches":
            continue
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"field {name}")
    # launch accounting: unfused = 2 phase programs per iteration; fused
    # pallas = exactly ceil(iters / chunk) kernel launches (the early exit
    # never pays for an extra empty launch); fused xla = one traced
    # compute body per iteration (2x fewer programs, not chunked)
    iters = int(a.iters)
    assert int(a.launches) == 2 * iters
    want = -(-iters // chunk) if backend == "pallas" else iters
    assert int(b.launches) == want


def _consistent_region(n, m, seed):
    """A *valid* single-region network (true reverse slots, zero labels) —
    the engine provably terminates on it, unlike on _random_region's
    arbitrary labels and masks, so it can run to convergence."""
    from repro.core.graph import build, intra_mask
    from repro.data.grids import random_sparse

    p = random_sparse(n, m, seed=seed)
    meta, state, _ = build(p, np.zeros(n, np.int64))
    sq = lambda a: a[0]
    return dict(
        cf=sq(state.cf), sink_cf=sq(state.sink_cf), excess=sq(state.excess),
        lab=jnp.zeros_like(sq(state.sink_cf)),
        nbr_local=sq(state.nbr_local), rev_slot=sq(state.rev_slot),
        intra=sq(intra_mask(state)), emask=sq(state.emask),
        vmask=sq(state.vmask),
        cross_pushable=jnp.zeros_like(sq(state.emask)),
        cross_lab=jnp.zeros_like(sq(state.nbr_local)),
    ), meta.num_vertices


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fused_early_exit_convergence(backend):
    """On a consistent network the fused driver runs to convergence with an
    in-kernel early exit: identical final state and iteration count as the
    unfused engine, and exactly ceil(iters/chunk) launches — the early exit
    stops mid-chunk instead of padding to a chunk multiple."""
    r, n = _consistent_region(12, 24, seed=4)
    kw = dict(nbr_local=r["nbr_local"], rev_slot=r["rev_slot"],
              intra=r["intra"], emask=r["emask"], vmask=r["vmask"],
              cross_pushable=r["cross_pushable"], cross_lab=r["cross_lab"],
              d_inf=n, sink_open=True, max_iters=None)
    a = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend="xla", **kw)
    iters = int(a.iters)
    assert iters > 0
    # no active vertex left: the run converged rather than hitting a cap
    assert not bool(((a.excess > 0) & (a.lab < n) & r["vmask"]).any())
    for chunk in (8, iters + 5):     # mid-chunk exit / single-launch exit
        b = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                         backend=backend, chunk_iters=chunk, **kw)
        for name, x, y in zip(a._fields, a, b):
            if name == "launches":
                continue
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"field {name}")
        want = -(-iters // chunk) if backend == "pallas" else iters
        assert int(b.launches) == want


def test_fused_pallas_vmem_fallback():
    """A region over the VMEM budget must fall back to the blocked two-phase
    path (launch accounting shows 2/iteration) and stay bit-exact."""
    V, E = 33, 5
    r = _random_region(V, E, seed=7 * V + E)
    kw = _engine_kwargs(r, V, max_iters=16)
    a = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend="pallas", block_v=8, **kw)
    b = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                     backend="pallas", block_v=8, chunk_iters=8,
                     vmem_budget_bytes=1, **kw)
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"field {name}")
    assert int(b.launches) == 2 * int(b.iters)


def test_fused_engine_run_batched_matches_scalar_kernel():
    """The grid-over-regions kernel (grid=(K,)) is bit-identical, region by
    region, to K separate single-region fused kernel launches — including a
    region whose per-region iteration budget is exhausted (limit 0)."""
    rng = np.random.RandomState(0)
    K, V, E = 3, 16, 4
    mk = lambda *s, hi=10: jnp.asarray(rng.randint(0, hi, s), jnp.int32)
    lab, cf = mk(K, V, hi=8), mk(K, V, E, hi=50)
    sink, exc = mk(K, V, hi=20), mk(K, V, hi=40)
    nbr, rev = mk(K, V, E, hi=V), mk(K, V, E, hi=E)
    intra = jnp.asarray(rng.rand(K, V, E) < 0.8, jnp.int32)
    pushable = jnp.asarray(rng.rand(K, V, E) < 0.9, jnp.int32)
    clab = mk(K, V, E, hi=6)
    vmask = jnp.asarray(rng.rand(K, V) < 0.95, jnp.int32)
    d_inf, limit = 18, jnp.asarray([5, 0, 9], jnp.int32)
    got = fused_engine_run_batched(lab, cf, sink, exc, nbr, rev, intra,
                                   pushable, clab, vmask, d_inf, limit,
                                   interpret=True)
    for k in range(K):
        want = fused_engine_run(lab[k], cf[k], sink[k], exc[k], nbr[k],
                                rev[k], intra[k], pushable[k], clab[k],
                                vmask[k], d_inf, limit[k], interpret=True)
        for i, (x, y) in enumerate(zip([o[k] for o in got], want)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"region {k} output {i}")


@pytest.mark.parametrize("backend,chunk",
                         [("xla", None), ("xla", 8), ("pallas", 8)],
                         ids=["xla-unfused", "xla-fused", "pallas-fused"])
def test_push_relabel_batched_matches_vmapped_scalar(backend, chunk):
    """The batched engine entry point is bit-identical, per region, to
    jax.vmap of the scalar engine on every state field; only the launch
    accounting becomes global (1 per chunk trip on fused pallas)."""
    rng = np.random.RandomState(5)
    K, V, E = 3, 16, 4
    regions = [_random_region(V, E, seed=100 + k) for k in range(K)]
    stack = lambda name: jnp.stack([r[name] for r in regions])
    kw = dict(nbr_local=stack("nbr_local"), rev_slot=stack("rev_slot"),
              intra=stack("intra"), emask=stack("emask"),
              vmask=stack("vmask"), cross_pushable=stack("cross_pushable"),
              cross_lab=stack("cross_lab"), d_inf=V + 2, sink_open=True,
              max_iters=16)
    got = push_relabel_batched(stack("cf"), stack("sink_cf"),
                               stack("excess"), stack("lab"),
                               backend=backend, chunk_iters=chunk, **kw)
    launches = 0
    for k, r in enumerate(regions):
        want = push_relabel(r["cf"], r["sink_cf"], r["excess"], r["lab"],
                            nbr_local=r["nbr_local"], rev_slot=r["rev_slot"],
                            intra=r["intra"], emask=r["emask"],
                            vmask=r["vmask"],
                            cross_pushable=r["cross_pushable"],
                            cross_lab=r["cross_lab"], d_inf=V + 2,
                            sink_open=True, max_iters=16, backend=backend,
                            chunk_iters=chunk)
        launches += int(want.launches)
        for name, x, y in zip(want._fields, got, want):
            if name == "launches":
                continue
            np.testing.assert_array_equal(np.asarray(x[k]), np.asarray(y),
                                          err_msg=f"region {k} field {name}")
    if backend == "pallas" and chunk:
        # grid-over-regions: one launch per chunk trip covers every region,
        # so the dispatch count is the busiest region's ceil(iters/chunk)
        # instead of the sum over regions
        want_trips = max(-(-int(it) // chunk)
                         for it in np.asarray(got.iters))
        assert int(got.launches) == want_trips
    else:
        assert int(got.launches) == launches


def test_push_relabel_phase_respects_blocking():
    """Cross arcs marked non-pushable must get no flow and no relabel use."""
    V, E = 8, 3
    rng = np.random.RandomState(0)
    cf = jnp.asarray(rng.randint(1, 10, (V, E)), jnp.int32)
    nbr = jnp.asarray(rng.randint(0, V, (V, E)), jnp.int32)
    intra = jnp.zeros((V, E), jnp.int32)          # all cross
    pushable = jnp.zeros((V, E), jnp.int32)       # all blocked
    cross_lab = jnp.zeros((V, E), jnp.int32)
    lab = jnp.ones((V,), jnp.int32)
    excess = jnp.full((V,), 5, jnp.int32)
    sink_cf = jnp.zeros((V,), jnp.int32)
    delta, new_lab = push_relabel_phase(
        lab, cf, sink_cf, excess, nbr, intra, pushable, cross_lab, 16,
        block_v=8, interpret=True)
    assert int(jnp.sum(delta)) == 0
    assert (np.asarray(new_lab) == 16).all()      # relabel straight to cap
