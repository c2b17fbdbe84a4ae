"""Kernel-vs-oracle validation (Pallas interpret mode on CPU).

Per instructions: sweep shapes/dtypes per kernel and assert_allclose against
the ref.py pure-jnp oracle.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import operators as om
from repro.core.l0 import compute_gram_stats, score_tuples_qr
from repro.core.sis import TaskLayout, build_score_context
from repro.kernels import ops as kops
from repro.kernels.ref import fused_gen_sis_ref, l0_pair_sse_ref, solve3_sse


def _ctx_pair(resid, layout, s, s_pad):
    ctx = build_score_context(resid, layout)
    ctx_pad = build_score_context(resid, layout, s_pad=s_pad)
    return ctx, ctx_pad


def _oracle_scores(op_id, xa, xb, ctx_pad, s, l_b=1e-5, u_b=1e8):
    s_pad = ctx_pad.s_pad
    ap = jnp.full((xa.shape[0], s_pad), 1.0, jnp.float64).at[:, :s].set(xa)
    bp = jnp.full((xb.shape[0], s_pad), 1.0, jnp.float64).at[:, :s].set(xb)
    return np.array(fused_gen_sis_ref(
        op_id, ap, bp,
        jnp.asarray(ctx_pad.membership, jnp.float64),
        jnp.asarray(ctx_pad.y_tilde, jnp.float64),
        jnp.asarray(ctx_pad.counts, jnp.float64),
        ctx_pad.n_residuals, l_b, u_b,
    ))


OPS_SWEEP = [om.ADD, om.SUB, om.MUL, om.DIV, om.ABS_DIFF, om.LOG, om.SQRT,
             om.SQ, om.CB, om.INV, om.EXP, om.NEG_EXP, om.SIX_POW, om.CBRT]


@pytest.mark.parametrize("op_id", OPS_SWEEP)
def test_fused_sis_all_ops(rng, op_id):
    b, s, nf = 100, 156, 30
    x = rng.uniform(0.5, 3.0, (nf, s))
    ia, ib = rng.integers(0, nf, b), rng.integers(0, nf, b)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], [75, 81]))
    resid = rng.normal(size=(2, s))
    ctx, ctx_pad = _ctx_pair(resid, layout, s, ((s + 127) // 128) * 128)
    got = np.array(kops.fused_gen_sis(
        op_id, jnp.asarray(x[ia], jnp.float32), jnp.asarray(x[ib], jnp.float32),
        ctx, 1e-5, 1e8))
    want = _oracle_scores(op_id, x[ia], x[ib], ctx_pad, s)
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    f = np.isfinite(want)
    np.testing.assert_allclose(got[f], want[f], atol=5e-6)


@pytest.mark.parametrize("b,s,tasks,n_res,block", [
    (1, 8, 1, 1, 128),       # minimal
    (37, 100, 1, 3, 128),    # unaligned batch
    (256, 129, 2, 1, 128),   # s just over one lane tile
    (300, 400, 3, 2, 256),   # multi-task, multi-residual
    (512, 2400, 1, 10, 512), # kaggle-sized samples, 10 residuals (paper)
])
def test_fused_sis_shape_sweep(rng, b, s, tasks, n_res, block):
    nf = 20
    x = rng.uniform(0.5, 3.0, (nf, s))
    ia, ib = rng.integers(0, nf, b), rng.integers(0, nf, b)
    ids = np.sort(rng.integers(0, tasks, s))
    layout = TaskLayout.from_task_ids(ids) if tasks > 1 else TaskLayout.single(s)
    resid = rng.normal(size=(n_res, s))
    ctx, ctx_pad = _ctx_pair(resid, layout, s, ((s + 127) // 128) * 128)
    got = np.array(kops.fused_gen_sis(
        om.MUL, jnp.asarray(x[ia], jnp.float32), jnp.asarray(x[ib], jnp.float32),
        ctx, 1e-5, 1e8, block_b=block))
    want = _oracle_scores(om.MUL, x[ia], x[ib], ctx_pad, s)
    assert got.shape == (b,)
    f = np.isfinite(want)
    np.testing.assert_allclose(got[f], want[f], atol=5e-6)


def test_fused_sis_flags_invalid(rng):
    s = 65  # odd point count => linspace contains an exact zero
    x = np.stack([np.linspace(-1, 1, s),            # zero divisor value
                  rng.uniform(0.5, 1.0, s),
                  np.full(s, 2.0)])                 # constant -> zero variance
    layout = TaskLayout.single(s)
    ctx = build_score_context(rng.normal(size=(1, s)), layout)
    got = np.array(kops.fused_gen_sis(
        om.DIV, jnp.asarray(x[[1, 2]], jnp.float32), jnp.asarray(x[[0, 1]], jnp.float32),
        ctx, 1e-5, 1e8))
    assert got[0] == -np.inf       # b/a has inf at the zero crossing
    assert np.isfinite(got[1])
    got2 = np.array(kops.fused_gen_sis(
        om.MUL, jnp.asarray(x[[2]], jnp.float32), jnp.asarray(x[[2]], jnp.float32),
        ctx, 1e-5, 1e8))
    assert got2[0] == -np.inf      # constant*constant -> zero variance


# ---------------------------------------------------------------------------
# ℓ0 tile kernel
# ---------------------------------------------------------------------------

def test_solve3_closed_form_matches_linalg(rng):
    for _ in range(50):
        m3 = rng.normal(size=(3, 3))
        m3 = m3 @ m3.T + 3 * np.eye(3)
        r = rng.normal(size=3)
        yty = float(rng.uniform(10, 20))
        c = np.linalg.solve(m3, r)
        want = yty - c @ r
        got = float(solve3_sse(
            m3[0, 0], m3[1, 1], m3[2, 2], m3[0, 1], m3[0, 2], m3[1, 2],
            r[0], r[1], r[2], yty))
        np.testing.assert_allclose(got, max(want, 0.0), rtol=1e-9)


def test_l0_pair_sse_ref_matches_qr(rng):
    m, s = 20, 90
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], 45))
    pairs = np.stack(np.triu_indices(m, 1), 1).astype(np.int32)
    got = np.array(l0_pair_sse_ref(jnp.asarray(x), jnp.asarray(y),
                                   layout.slices, jnp.asarray(pairs)))
    want = np.array(score_tuples_qr(jnp.asarray(x), jnp.asarray(y), layout,
                                    jnp.asarray(pairs)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_l0_score_pairs_gram_gather(rng):
    m, s = 25, 80
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    layout = TaskLayout.single(s)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    pairs = np.stack(np.triu_indices(m, 1), 1).astype(np.int32)
    got = np.array(kops.l0_score_pairs(stats, jnp.asarray(pairs)))
    want = np.array(score_tuples_qr(jnp.asarray(x), jnp.asarray(y), layout,
                                    jnp.asarray(pairs)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("m,s,tasks,block", [
    (50, 60, 1, 128),
    (130, 156, 2, 128),    # unaligned m, multi-task (thermal-like)
    (300, 156, 2, 128),
    (200, 333, 3, 256),    # unaligned samples, 3 tasks
])
def test_l0_search_tiled_exact_topk(rng, m, s, tasks, block):
    x = rng.uniform(0.5, 3.0, (m, s))
    ids = np.sort(rng.integers(0, tasks, s))
    layout = TaskLayout.from_task_ids(ids) if tasks > 1 else TaskLayout.single(s)
    y = 2 * x[m // 3] * x[m // 2] + rng.normal(0, 0.3, s)
    tuples, sses, n_eval = kops.l0_search_tiled(x, y, layout, n_keep=10,
                                                block=block)
    pairs = np.stack(np.triu_indices(m, 1), 1).astype(np.int32)
    ref = np.array(score_tuples_qr(jnp.asarray(x), jnp.asarray(y), layout,
                                   jnp.asarray(pairs)))
    order = np.argsort(ref, kind="stable")[:10]
    assert np.array_equal(tuples, pairs[order].astype(np.int64))
    np.testing.assert_allclose(sses, ref[order], rtol=1e-5)
    assert n_eval == m * (m - 1) // 2


# ---------------------------------------------------------------------------
# ℓ0 Gram-gather kernel (widths >= 3)
# ---------------------------------------------------------------------------

def _ordered(tuples, order, rng):
    """The tuple block in one of the orders a kernel must score: the
    whole lex space, a mid-space rank range whose first and last runs are
    cut, reversed (every run of length one) or shuffled."""
    if order == "lex":
        return tuples
    if order == "mid":
        return tuples[5 : len(tuples) - 3]
    if order == "reversed":
        return tuples[::-1]
    return tuples[rng.permutation(len(tuples))]


TUPLE_ORDERS = ["lex", "mid", "reversed", "shuffled"]


@pytest.mark.parametrize("order", TUPLE_ORDERS)
@pytest.mark.parametrize("m,s,tasks,width,block_t", [
    (10, 40, 1, 3, 128),     # minimal single-task
    (14, 156, 2, 3, 128),    # thermal-like multi-task
    (14, 60, 2, 4, 128),     # width 4
    (20, 90, 1, 4, 256),     # bigger tile
    (12, 333, 3, 3, 128),    # unaligned samples, 3 tasks
    (12, 60, 1, 5, 128),     # width 5 (generic unrolled elimination)
    (10, 50, 2, 6, 128),     # width 6, multi-task
])
def test_l0_gather_kernel_matches_oracle(rng, m, s, tasks, width, block_t,
                                         order):
    from repro.kernels.ref import l0_gather_sse_ref

    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[3] - x[7] + 0.1 * rng.normal(size=s)
    ids = np.sort(rng.integers(0, tasks, s))
    layout = TaskLayout.from_task_ids(ids) if tasks > 1 else TaskLayout.single(s)
    tuples = _ordered(np.asarray(
        list(__import__("itertools").combinations(range(m), width)), np.int32),
        order, rng)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    pack = kops.pack_gram_fp32(stats)
    got = np.asarray(kops.l0_score_tuples(pack, jnp.asarray(tuples),
                                          block_t=block_t, interpret=True))
    oracle = np.asarray(l0_gather_sse_ref(
        pack["gram"], pack["fsum"], pack["bvec"], pack["scal"],
        jnp.asarray(tuples)))
    want = np.asarray(score_tuples_qr(jnp.asarray(x), jnp.asarray(y), layout,
                                      jnp.asarray(tuples)))
    assert got.shape == (len(tuples),)
    # kernel vs pure-jnp oracle: same math, fp32 accumulation-order noise
    np.testing.assert_allclose(got, oracle, rtol=1e-3, atol=1e-4)
    # fp32 pre-pass vs fp64 QR: a ranking-quality bound, not bit equality —
    # phase 2 (backend rescore) restores exact values for the winners
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-9)
    assert np.quantile(rel, 0.99) < 2e-2
    assert np.argmin(got) == np.argmin(want)


def test_l0_run_table_matches_lex_runs():
    """A lex rank range's run table: one row per (n-1)-prefix, the runs at
    both ends cut, each row's window its stretch of last indices."""
    import itertools

    from repro.kernels.l0_gather import count_runs, panel_rows, run_table

    m, n = 9, 3
    tuples = np.asarray(list(itertools.combinations(range(m), n)), np.int32)
    blk = tuples[4:61]
    runs = int(count_runs(jnp.asarray(blk.T), len(blk))[0])
    prefixes = [tuple(p) for p in blk[:, :2]]
    assert runs == len(set(prefixes))
    table, run_id = run_table(jnp.asarray(blk.T), len(blk), panel_rows(runs))
    table, run_id = np.asarray(table), np.asarray(run_id)
    assert np.all(np.diff(run_id) >= 0) and run_id[-1] == runs - 1
    for r in range(runs):
        rows = blk[run_id == r]
        assert len({tuple(p) for p in rows[:, :2]}) == 1
        assert tuple(table[r, :2]) == tuple(rows[0, :2])
        assert (table[r, 2], table[r, 3]) == (rows[0, 2], rows[-1, 2] + 1)
    assert np.all(table[runs:, 2] == table[runs:, 3])     # empty windows
    # a chunked count sees each chunk's first tuple open a run
    per = np.asarray(count_runs(jnp.asarray(tuples[:56].T), 50, shards=4))
    want = [len({tuple(p) for p in c[:, :2]})
            for c in np.split(tuples[:56], 4)]
    want[-1] = len({tuple(p) for p in tuples[42:50, :2]})
    assert list(per) == want


@pytest.mark.parametrize("collinearity", [1e-2, 1e-4, 1e-6])
def test_l0_gather_bound_never_exceeds_exact_sse(rng, collinearity):
    """The kernel's output is a certified lower bound: on near-collinear
    features, fits from near-exact to noisy and feature scales over six
    decades, no tuple's bound exceeds its exact (fp64 QR) SSE."""
    import itertools

    from repro.kernels.ref import l0_gather_sse_ref

    m, s = 12, 80
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], [35, 45]))
    for _ in range(8):
        x = rng.uniform(0.5, 3.0, (m, s)) * 10.0 ** rng.uniform(-3, 3, (m, 1))
        for j in range(4):
            a, b, c = rng.choice(m, 3, replace=False)
            x[c] = (rng.normal() * x[a] + rng.normal() * x[b] * (j % 2)
                    + collinearity * x[c].std() * rng.normal(size=s))
        y = (rng.normal() * x[3] / x[3].std()
             + rng.normal() * x[7] / x[7].std() + 5.0
             + 10.0 ** rng.uniform(-8, 0) * rng.normal(size=s))
        pack = kops.pack_gram(compute_gram_stats(x, y, layout))
        for width in (3, 4):
            tuples = np.asarray(list(itertools.combinations(range(m), width)),
                                np.int32)
            bound = np.asarray(l0_gather_sse_ref(
                pack["gram"], pack["fsum"], pack["bvec"], pack["scal"],
                jnp.asarray(tuples)), np.float64)
            exact = np.zeros(len(tuples))
            for lo, hi in layout.slices:
                design = np.concatenate(
                    [x[tuples, lo:hi].transpose(0, 2, 1),
                     np.ones((len(tuples), hi - lo, 1))], axis=2)
                q, _ = np.linalg.qr(design)
                fit = np.einsum("bij,bj->bi", q,
                                np.einsum("bij,i->bj", q, y[lo:hi]))
                exact += ((y[lo:hi] - fit) ** 2).sum(axis=1)
            assert np.all(bound <= exact * (1 + 1e-9) + 1e-12 * exact.max())


def test_l0_gather_padding_is_inert(rng):
    """A block cut mid-run (131 of the tuples) scores each of its tuples
    as the whole block does."""
    m, s = 11, 50
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    layout = TaskLayout.single(s)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    pack = kops.pack_gram_fp32(stats)
    tuples = np.asarray(
        list(__import__("itertools").combinations(range(m), 3)), np.int32)
    full = np.asarray(kops.l0_score_tuples(pack, jnp.asarray(tuples),
                                           block_t=128, interpret=True))
    ragged = np.asarray(kops.l0_score_tuples(pack, jnp.asarray(tuples[:131]),
                                             block_t=128, interpret=True))
    np.testing.assert_array_equal(ragged, full[:131])


def test_l0_gather_short_panel_leaves_no_bound(rng):
    """A panel lower than the block's run count scores the runs it holds
    as a tall enough one does, and gives the runs past it the bound -inf
    (no bound), never another tuple's entry."""
    import itertools

    from repro.kernels.l0_gather import (
        count_runs, l0_gather_tuples_pallas, panel_rows, run_table,
    )

    m, s, n = 11, 50, 3
    x = rng.uniform(0.5, 3.0, (m, s))
    pack = kops.pack_gram_fp32(compute_gram_stats(
        jnp.asarray(x), jnp.asarray(rng.normal(size=s)), TaskLayout.single(s)))
    tt = jnp.asarray(np.asarray(list(itertools.combinations(range(m), n)),
                                np.int32).T)
    b = tt.shape[1]
    runs = int(count_runs(tt, b)[0])
    assert runs > 8

    def score(rows):
        return np.asarray(l0_gather_tuples_pallas(
            tt, pack["gram"], pack["fsum"], pack["bvec"], pack["scal"], n=n,
            block_t=128, rows=rows, interpret=True))

    full, short = score(panel_rows(runs)), score(8)
    held = np.asarray(run_table(tt, b, 8)[1]) < 8
    assert 0 < held.sum() < b
    np.testing.assert_array_equal(short[held], full[held])
    assert np.all(short[~held] == -np.inf)


# ---------------------------------------------------------------------------
# reduced top-k epilogues (kernels/topk.py + the *_topk wrappers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("largest", [True, False])
def test_block_topk_matches_stable_sort(rng, largest):
    from repro.kernels.topk import block_topk

    scores = rng.normal(size=(1, 256)).astype(np.float32)
    scores[0, 77] = scores[0, 13]  # exact tie -> lowest position must win
    k, k_pad = 9, 128
    vals, pos = jax.jit(block_topk, static_argnums=(1, 2, 3))(
        jnp.asarray(scores), k, k_pad, largest)
    vals, pos = np.asarray(vals)[0], np.asarray(pos)[0]
    key = -scores[0] if largest else scores[0]
    want = np.argsort(key, kind="stable")[:k]
    assert np.array_equal(pos[:k], want)
    np.testing.assert_array_equal(vals[:k], scores[0][want])
    # sentinel lanes: +-inf values, pos -1
    assert np.all(np.isinf(vals[k:]))
    assert np.all(pos[k:] == -1)


def test_merge_block_topk_tie_order():
    from repro.kernels.topk import merge_block_topk

    # two blocks with an exact cross-block tie: lower global index must win
    vals = jnp.asarray([[5.0, 3.0, -np.inf], [5.0, 4.0, -np.inf]], jnp.float32)
    idx = jnp.asarray([[10, 11, -1], [20, 21, -1]], jnp.int32)
    v, i = merge_block_topk(vals, idx, 3, largest=True)
    assert list(np.asarray(i)) == [10, 20, 21]
    np.testing.assert_array_equal(np.asarray(v), [5.0, 5.0, 4.0])


def test_fused_sis_topk_matches_reduce_host(rng):
    from repro.core.sis import ReducedBlock

    b, s, nf = 300, 156, 30
    x = rng.uniform(0.5, 3.0, (nf, s))
    ia, ib = rng.integers(0, nf, b), rng.integers(0, nf, b)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], [75, 81]))
    ctx = build_score_context(rng.normal(size=(2, s)), layout)
    a1 = jnp.asarray(x[ia], jnp.float32)
    b1 = jnp.asarray(x[ib], jnp.float32)
    full = np.array(kops.fused_gen_sis(om.MUL, a1, b1, ctx, 1e-5, 1e8,
                                       block_b=128))
    ref = ReducedBlock.reduce_host(full, 25)
    vals, idx = kops.fused_gen_sis_topk(om.MUL, a1, b1, ctx, 1e-5, 1e8,
                                        n_keep=25, block_b=128, epilogue_k=32)
    assert np.array_equal(idx, ref.indices)
    np.testing.assert_allclose(vals, ref.scores, rtol=1e-6)
    assert np.all(np.isfinite(vals))


def test_fused_sis_topk_padding_never_selected(rng):
    """131 rows over block_b=128: padding rows must not reach the winners."""
    b, s, nf = 131, 100, 12
    x = rng.uniform(0.5, 3.0, (nf, s))
    ia, ib = rng.integers(0, nf, b), rng.integers(0, nf, b)
    ctx = build_score_context(rng.normal(size=(1, s)), TaskLayout.single(s))
    vals, idx = kops.fused_gen_sis_topk(
        om.ADD, jnp.asarray(x[ia], jnp.float32), jnp.asarray(x[ib], jnp.float32),
        ctx, 1e-5, 1e8, n_keep=131, block_b=128, epilogue_k=128)
    assert np.all((idx >= 0) & (idx < b))
    assert np.all(np.isfinite(vals))


@pytest.mark.parametrize("width", [3, 5])
def test_l0_topk_tuples_matches_full(rng, width):
    m, s = 12, 70
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[3] - x[7] + 0.1 * rng.normal(size=s)
    layout = TaskLayout.single(s)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout)
    pack = kops.pack_gram_fp32(stats)
    tuples = np.asarray(
        list(__import__("itertools").combinations(range(m), width)), np.int32)
    full = np.asarray(kops.l0_score_tuples(pack, jnp.asarray(tuples),
                                           block_t=128, interpret=True))
    order = np.argsort(full, kind="stable")[:10]
    sses, idx, floor = kops.l0_topk_tuples(
        pack, jnp.asarray(tuples), n_keep=10, block_t=128, interpret=True)
    # same fp32 math but a different XLA fusion graph: indices must agree
    # exactly, values up to FMA/fusion ulp noise (fp64 rescore is phase 2)
    assert np.array_equal(idx, order)
    np.testing.assert_allclose(sses, full[order], rtol=1e-4)
    # the floor lies under every bound left out, and at the merge's cut
    left_out = np.setdiff1d(np.arange(len(tuples)), idx)
    assert floor <= full[left_out].min() * (1 + 1e-6)
    assert floor == pytest.approx(sses[-1], rel=1e-6) or floor < sses[-1]
    # a block cut mid-run (131 tuples): keeping all of them leaves nothing
    # out, and no position past the block surfaces
    sses2, idx2, floor2 = kops.l0_topk_tuples(
        pack, jnp.asarray(tuples[:131]), n_keep=131, block_t=128,
        interpret=True)
    assert np.all((idx2 >= 0) & (idx2 < 131))
    assert np.all(np.isfinite(sses2))
    assert floor2 == np.inf


@pytest.mark.parametrize("order", ["lex", "mid", "shuffled"])
def test_l0_topk_tuples_tie_order_matches_stable_sort(rng, order):
    """Survivors, floor and tie order of the reduced kernel path equal a
    stable sort of the full bound vector, on blocks whose first and last
    runs are cut and with exact ties (two identical features)."""
    import itertools

    m, s, n_keep = 13, 64, 40
    x = rng.uniform(0.5, 3.0, (m, s))
    x[9] = x[4]                      # every tuple through 4 ties its twin
    y = x[4] - 0.5 * x[7] + 0.1 * rng.normal(size=s)
    pack = kops.pack_gram_fp32(compute_gram_stats(
        jnp.asarray(x), jnp.asarray(y), TaskLayout.single(s)))
    tuples = _ordered(np.asarray(list(itertools.combinations(range(m), 3)),
                                 np.int32), order, rng)
    blk = jnp.asarray(tuples)
    full = np.asarray(kops.l0_score_tuples(pack, blk, block_t=8,
                                           interpret=True))
    want = np.argsort(full, kind="stable")[:n_keep]
    vals, idx, floor = kops.l0_topk_tuples(pack, blk, n_keep=n_keep,
                                           block_t=8, interpret=True)
    assert len(np.unique(full[want])) < n_keep   # ties among the winners
    assert np.array_equal(idx, want)
    np.testing.assert_array_equal(vals, full[want])
    assert floor == full[want[-1]]


def test_fused_sis_topk_bf16_winner_overlap(rng):
    """bf16 operand generation: winner *set* stays close to fp32 (the
    backend's fp64 rescore pins exact ranking downstream)."""
    b, s, nf = 256, 128, 20
    x = rng.uniform(0.5, 3.0, (nf, s))
    ia, ib = rng.integers(0, nf, b), rng.integers(0, nf, b)
    ctx = build_score_context(rng.normal(size=(2, s)), TaskLayout.single(s))
    a1, b1 = jnp.asarray(x[ia]), jnp.asarray(x[ib])
    _, idx32 = kops.fused_gen_sis_topk(
        om.MUL, a1, b1, ctx, 1e-5, 1e8, n_keep=10, block_b=128,
        dtype=jnp.float32)
    _, idx16 = kops.fused_gen_sis_topk(
        om.MUL, a1, b1, ctx, 1e-5, 1e8, n_keep=20, block_b=128,
        dtype=jnp.bfloat16)
    assert np.all((idx16 >= 0) & (idx16 < b))
    # fp32 top-10 contained in bf16 top-20 (rank noise < 2x margin)
    assert len(set(idx32.tolist()) - set(idx16.tolist())) == 0


def test_pack_gram_dtype_variants(rng):
    m, s = 10, 64
    x = rng.uniform(0.5, 3.0, (m, s))
    y = rng.normal(size=s)
    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y),
                               TaskLayout.single(s))
    p32 = kops.pack_gram(stats, jnp.float32)
    p16 = kops.pack_gram(stats, jnp.bfloat16)
    assert p32["dtype"] == "float32" and p16["dtype"] == "bfloat16"
    # bf16 planes: three carry the fp32 rounding exactly, one rounds to bf16
    assert p32["gram"].shape[0] == 3 and p16["gram"].shape[0] == 1
    assert p32["gram"].dtype == p16["gram"].dtype == jnp.bfloat16
    # scal stays fp32 in both: the solve epilogue accumulates in fp32
    assert p16["scal"].dtype == jnp.float32
    g32 = np.asarray(stats.gram, np.float32)
    summed = np.zeros_like(g32)
    for plane in np.asarray(p32["gram"], np.float32):
        summed = summed + plane[:, :m, :m]
    np.testing.assert_array_equal(summed, g32)
    np.testing.assert_allclose(np.asarray(p16["gram"][0], np.float32)[:, :m, :m],
                               g32, rtol=2e-2, atol=1e-2)


def test_l0_search_tiled_planted(rng):
    m, s = 140, 96
    x = rng.uniform(0.5, 3.0, (m, s))
    y = -1.5 * x[7] + 4.0 * x[100]
    tuples, sses, _ = kops.l0_search_tiled(x, y, TaskLayout.single(s), n_keep=3)
    assert tuple(tuples[0]) == (7, 100)
    assert sses[0] < 1e-9
