import numpy as np
import jax.numpy as jnp
import pytest
from _hyp import given, settings, strategies as st

from repro.core.l0 import (
    compute_gram_stats, l0_search, n_models, score_tuples_gram,
    score_tuples_qr, tuple_blocks,
)
from repro.core.sis import TaskLayout


def lstsq_sse(x, y, slices, tup):
    """numpy oracle: per-task LSQ with intercept, total SSE."""
    total = 0.0
    for lo, hi in slices:
        a = np.concatenate([x[list(tup), lo:hi].T,
                            np.ones((hi - lo, 1))], axis=1)
        c, *_ = np.linalg.lstsq(a, y[lo:hi], rcond=None)
        r = y[lo:hi] - a @ c
        total += float(r @ r)
    return total


@pytest.mark.parametrize("n_dim", [1, 2, 3])
@pytest.mark.parametrize("tasks", [1, 2])
def test_gram_equals_qr_equals_numpy(rng, n_dim, tasks):
    m, s = 12, 70
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    ids = np.repeat(np.arange(tasks), s // tasks + 1)[:s]
    layout = TaskLayout.from_task_ids(ids)
    tuples = np.asarray(list(__import__("itertools").combinations(range(m), n_dim)),
                        np.int32)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    g = np.array(score_tuples_gram(stats, jnp.asarray(tuples)))
    q = np.array(score_tuples_qr(jnp.asarray(x), jnp.asarray(y), layout,
                                 jnp.asarray(tuples)))
    ref = np.array([lstsq_sse(x, y, layout.slices, t) for t in tuples])
    np.testing.assert_allclose(g, ref, rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(q, ref, rtol=1e-6, atol=1e-8)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(4, 10), seed=st.integers(0, 10_000))
def test_gram_qr_argmin_agree_property(m, seed):
    rng = np.random.default_rng(seed)
    s = 40
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    layout = TaskLayout.single(s)
    pairs = np.stack(np.triu_indices(m, 1), 1).astype(np.int32)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    g = np.array(score_tuples_gram(stats, jnp.asarray(pairs)))
    q = np.array(score_tuples_qr(jnp.asarray(x), jnp.asarray(y), layout,
                                 jnp.asarray(pairs)))
    assert np.argmin(g) == np.argmin(q)


def test_n_models_matches_fig1d():
    assert n_models(10, 1) == 10
    assert n_models(10, 2) == 45
    assert n_models(5000, 2) == 12_497_500  # SIS-sized spaces stay tractable


@pytest.mark.parametrize("n_dim", [1, 2, 3, 4])
def test_tuple_blocks_cover_exactly_once(n_dim):
    m, block = 9, 7
    seen = set()
    for blk in tuple_blocks(m, n_dim, block):
        assert blk.shape[1] == n_dim and len(blk) <= block
        for t in blk:
            assert tuple(t) not in seen
            assert all(t[i] < t[i + 1] for i in range(n_dim - 1))
            seen.add(tuple(t))
    assert len(seen) == n_models(m, n_dim)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64],
                         ids=["int32", "int64"])
@pytest.mark.parametrize("m,n", [(5, 3), (9, 3), (9, 4), (12, 2), (7, 1),
                                 (16, 4), (6, 5)])
def test_unranking_matches_itertools(m, n, dtype):
    """Device unranking is the exact lexicographic bijection in either
    integer width: rank r maps to the r-th tuple of
    ``itertools.combinations(range(m), n)``."""
    from repro.kernels.unrank import comb_exact, unrank_lex, unrank_lex_host

    want = np.asarray(list(__import__("itertools").combinations(range(m), n)),
                      np.int32)
    total = comb_exact(m, n)
    assert total == len(want) == n_models(m, n)
    got = unrank_lex(jnp.arange(total, dtype=dtype), m, n)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)
    for r in (0, 1, total // 2, total - 1):
        assert unrank_lex_host(r, m, n) == list(want[r])


def test_rank_dtype_follows_the_space():
    """int32 where the space fits it (thermal at rung 1), int64 where only
    int64 does (C(6000, 3)), the host from 2**62 on; each width-3 block
    counts the path it took."""
    from repro.core.l0 import TupleEnumerator
    from repro.kernels.unrank import (
        comb_exact, rank_dtype, unrank_lex, unrank_lex_host,
    )
    from repro.runtime import trace

    assert rank_dtype(600, 3) == np.int32
    assert rank_dtype(6000, 3) == np.int64
    m_host = round((6 * 2**62) ** (1 / 3))  # C(m, 3) ≈ m³/6 meets 2**62
    while comb_exact(m_host, 3) < 2**62:
        m_host += 1
    while comb_exact(m_host - 1, 3) >= 2**62:
        m_host -= 1
    assert rank_dtype(m_host - 1, 3) == np.int64
    assert rank_dtype(m_host, 3) is None
    # a space past int32 is refused in int32, never wrapped
    with pytest.raises(ValueError, match="int32"):
        unrank_lex(jnp.arange(4, dtype=jnp.int32), 6000, 3)

    total = comb_exact(6000, 3)
    ranks = [0, 1, total // 2, total - 2, total - 1]
    got = np.asarray(unrank_lex(jnp.asarray(ranks, jnp.int64), 6000, 3))
    assert [list(t) for t in got] == [unrank_lex_host(r, 6000, 3)
                                      for r in ranks]

    with trace.collecting() as rec:
        for m, block in ((600, 4096), (6000, 64), (m_host, 8)):
            enum = TupleEnumerator(m, 3, block)
            for bi in (0, enum.n_blocks // 2, enum.n_blocks - 1):
                lo = bi * block
                assert np.array_equal(np.asarray(enum.block_tuples(bi)),
                                      enum._host_block(lo, enum.count(bi)))
    assert rec.stats()["l0_enum"] == {
        3: {"device int32": 3, "device int64": 3, "host": 3}}


def test_enumerator_blocks_are_rank_addressable():
    """Block bi materializes exactly ranks [bi*block, bi*block+count) — the
    journal's resume contract — on both the device and host-exact paths."""
    from repro.core.l0 import TupleEnumerator

    m, n, block = 11, 3, 37
    want = np.asarray(list(__import__("itertools").combinations(range(m), n)),
                      np.int32)
    enum = TupleEnumerator(m, n, block)
    assert enum.total == len(want)
    for bi in range(enum.n_blocks):
        lo = bi * block
        blk = np.asarray(enum.block_tuples(bi))
        assert np.array_equal(blk, want[lo : lo + enum.count(bi)])
        host = enum._host_block(lo, enum.count(bi))
        assert np.array_equal(host, blk)


def test_l0_search_qr_degenerate_feature_not_dropped(rng):
    """A rank-deficient feature (all-zero column) must not poison its
    block: QR SSEs for tuples containing it rank last (inf, not NaN), and
    the merge-skip never discards a block holding the true winner."""
    m, s = 8, 40
    x = rng.uniform(0.5, 3.0, (m, s))
    x[2] = 0.0  # degenerate: QR normal equations go rank-deficient
    y = 2.0 * x[4] - 1.0 * x[5] + 0.01 * rng.normal(size=s)
    layout = TaskLayout.single(s)
    res = l0_search(x, y, layout, n_dim=2, n_keep=3, block=1000, method="qr")
    assert tuple(res.tuples[0]) == (4, 5)
    assert np.isfinite(res.sses[0])


def test_l0_search_legacy_engine_alias_warns(rng):
    m, s = 10, 30
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    with pytest.warns(DeprecationWarning, match="l0_search"):
        res = l0_search(x, y, TaskLayout.single(s), n_dim=2, n_keep=3,
                        block=16, engine="qr")
    ref = l0_search(x, y, TaskLayout.single(s), n_dim=2, n_keep=3,
                    block=16, method="qr")
    np.testing.assert_array_equal(res.tuples, ref.tuples)


@pytest.mark.parametrize("method", ["gram", "qr"])
def test_l0_search_finds_planted_pair(rng, method):
    m, s = 30, 60
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[4] - 3.0 * x[17] + 0.7
    res = l0_search(x, y, TaskLayout.single(s), n_dim=2, n_keep=5,
                    block=101, method=method)
    assert tuple(res.tuples[0]) == (4, 17)
    assert res.sses[0] < 1e-6
    assert res.n_evaluated == n_models(m, 2)
    assert (np.diff(res.sses) >= -1e-12).all()


def test_l0_search_topk_matches_bruteforce(rng):
    m, s = 16, 50
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    layout = TaskLayout.single(s)
    res = l0_search(x, y, layout, n_dim=2, n_keep=8, block=13)
    pairs = np.stack(np.triu_indices(m, 1), 1)
    ref = np.array([lstsq_sse(x, y, layout.slices, t) for t in pairs])
    order = np.argsort(ref, kind="stable")[:8]
    np.testing.assert_allclose(res.sses, ref[order], rtol=1e-6)
    assert {tuple(t) for t in res.tuples} == {tuple(pairs[i]) for i in order}


def test_multitask_coefficients_differ_per_task(rng):
    from repro.core.l0 import coefficients_for
    s = 80
    x = rng.uniform(0.5, 3.0, (5, s))
    ids = np.repeat([0, 1], 40)
    y = np.where(ids == 0, 2 * x[1] + 1, -3 * x[1] + 5)
    layout = TaskLayout.from_task_ids(ids)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    coefs, inter = coefficients_for(stats, [1])
    np.testing.assert_allclose(coefs[:, 0], [2.0, -3.0], rtol=1e-8)
    np.testing.assert_allclose(inter, [1.0, 5.0], rtol=1e-7)
