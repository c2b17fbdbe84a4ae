import numpy as np

from repro.core import FeatureSpace
from repro.core.units import Unit


def make_space(rng, on_the_fly=False, max_rung=2, ops=("add", "mul", "sq", "div")):
    x = rng.uniform(0.5, 3.0, size=(4, 64))
    return FeatureSpace(
        x, names=list("abcd"), op_names=ops, max_rung=max_rung,
        on_the_fly_last_rung=on_the_fly,
    )


def test_primary_features_registered(rng):
    fs = make_space(rng, max_rung=0)
    assert len(fs.features) == 4
    assert [f.expr for f in fs.features] == list("abcd")
    assert all(f.rung == 0 for f in fs.features)
    assert fs.values_matrix().shape == (4, 64)


def test_generation_grows_and_tracks_rungs(rng):
    fs = make_space(rng).generate()
    rungs = {f.rung for f in fs.features}
    assert rungs == {0, 1, 2}
    # fid == row invariant for materialized features
    for f in fs.features:
        assert f.row == f.fid


def test_unit_consistency_blocks_add(rng):
    x = rng.uniform(0.5, 3.0, size=(2, 32))
    basis = ("m", "s")
    units = [Unit.from_mapping({"m": 1}, basis), Unit.from_mapping({"s": 1}, basis)]
    fs = FeatureSpace(x, ["L", "T"], units=units, op_names=("add", "mul"),
                      max_rung=1).generate()
    exprs = [f.expr for f in fs.features if f.rung == 1]
    assert "(L + T)" not in exprs  # unit mismatch
    assert "(L * T)" in exprs
    assert fs.n_rejected["unit"] > 0


def test_value_duplicates_rejected(rng):
    x = rng.uniform(0.5, 3.0, size=(2, 32))
    x[1] = 2.0 * x[0]  # b = 2a is a scalar multiple of a -> same model span
    fs = FeatureSpace(x, ["a", "b"], op_names=("mul", "sq"), max_rung=1).generate()
    # primary b is deduped at registration; only a and a^2 survive
    assert [f.expr for f in fs.features] == ["a", "(a)^2"]
    assert fs.n_rejected["dup"] >= 1


def test_generated_duplicates_rejected(rng):
    x = rng.uniform(0.5, 3.0, size=(2, 32))
    fs = FeatureSpace(x, ["a", "b"], op_names=("mul", "div", "inv"),
                      max_rung=2).generate()
    # e.g. (a*b)*(1/a) duplicates b; inv(inv(a)) is blocked as redundant;
    # overall some dups must have been caught at rung 2
    assert fs.n_rejected["dup"] > 0
    # and no two surviving features are scalar multiples of each other
    v = fs.values_matrix()
    vc = v - v.mean(axis=1, keepdims=True)
    vn = vc / np.linalg.norm(vc, axis=1, keepdims=True)
    corr = np.abs(vn @ vn.T) - np.eye(len(vn))
    assert corr.max() < 1.0 - 1e-9


def test_domain_rule_prevents_div_by_straddling_zero(rng):
    x = np.stack([rng.uniform(0.5, 3.0, 32), rng.uniform(-1.0, 1.0, 32)])
    fs = FeatureSpace(x, ["a", "b"], op_names=("div",), max_rung=1).generate()
    exprs = [f.expr for f in fs.features if f.rung == 1]
    assert "(a / b)" not in exprs
    assert "(b / a)" in exprs


def test_bounds_reject_large_values(rng):
    x = rng.uniform(100.0, 1000.0, size=(2, 32))
    fs = FeatureSpace(x, ["a", "b"], op_names=("mul", "sq"), max_rung=2,
                      u_bound=1e5).generate()
    for f in fs.features:
        assert abs(f.vmax) <= 1e5 and abs(f.vmin) <= 1e5


def test_on_the_fly_defers_last_rung(rng):
    fs_mat = make_space(rng, on_the_fly=False).generate()
    fs_otf = make_space(rng, on_the_fly=True).generate()
    # lower rungs identical
    mat_r1 = {f.expr for f in fs_mat.features if f.rung <= 1}
    otf_r1 = {f.expr for f in fs_otf.features if f.rung <= 1}
    assert mat_r1 == otf_r1
    assert fs_otf.n_candidates_deferred > 0
    # deferred candidate count >= materialized rung-2 count (value rules not
    # yet applied to deferred ones)
    n_mat_r2 = sum(1 for f in fs_mat.features if f.rung == 2)
    assert fs_otf.n_candidates_deferred >= n_mat_r2


def test_candidate_batching_covers_all(rng):
    fs = make_space(rng, on_the_fly=True).generate()
    total = sum(len(b) for b in fs.iter_candidate_batches(7))
    assert total == fs.n_candidates_deferred
    for blk in fs.iter_candidate_batches(7):
        assert len(blk) <= 7


def test_materialize_candidate_roundtrip(rng):
    fs = make_space(rng, on_the_fly=True).generate()
    blk = fs.candidates[0]
    before = len(fs.features)
    f = fs.materialize_candidate(blk.op_id, int(blk.child_a[0]), int(blk.child_b[0]))
    assert f is not None and f.rung == fs.max_rung
    assert len(fs.features) == before + 1
    # re-materializing the same candidate is a duplicate
    assert fs.materialize_candidate(
        blk.op_id, int(blk.child_a[0]), int(blk.child_b[0])
    ) is None


def test_eval_candidates_validity_flags(rng):
    x = np.stack([np.linspace(-1, 1, 33), rng.uniform(0.5, 1.0, 33)])
    fs = FeatureSpace(x, ["a", "b"], op_names=("div",), max_rung=1)
    from repro.core.operators import DIV
    vals, valid = fs.eval_candidates(DIV, np.array([1]), np.array([0]))
    assert not valid[0]  # b/a crosses a zero denominator -> inf values


def _reference_child_sets(space, op_names):
    """Rung-2 child index sets of each operator of the plain reference
    (benchmarks/suite/reference.py) over its rung 0-1 ``space``, after its
    unit, domain and simplification rules and before value rules."""
    from benchmarks.suite import reference
    from benchmarks.suite.expr import OPS, SIMPLIFIES

    prev = np.nonzero(space.rungs == 1)[0]
    lower = np.nonzero(space.rungs == 0)[0]
    lo, hi = space.values.min(axis=1), space.values.max(axis=1)
    out = {}
    for name in op_names:
        op = OPS[name]
        out[name] = [
            c for c in reference._candidates(op, prev, lower)
            if not (op.arity == 1 and (name, space.roots[c[0]]) in SIMPLIFIES)
            and op.unit(*(space.units[i] for i in c)) is not None
            and op.domain(*((lo[i], hi[i]) for i in c))]
    return out


def test_rung2_child_sets_match_the_reference():
    """Before value rules, each operator's rung-2 child sets are the plain
    reference's, each once: every unordered pair of a commutative
    operator, pairs of a rung-1 feature with a primary among them, and
    every ordered pair of the others (five primaries with units)."""
    import _reference_case as case
    from benchmarks.suite import reference
    from repro.core import operators

    d = case.data()
    ops = case.CASE["op_names"]
    fs = FeatureSpace(d.x, d.names, [Unit(tuple(u), d.basis) for u in d.units],
                      op_names=ops, max_rung=2, l_bound=case.CASE["l_bound"],
                      u_bound=case.CASE["u_bound"], on_the_fly_last_rung=True,
                      engine="jnp").generate()
    space = reference.build_space(d.x, d.names, d.units, ops, 1,
                                  case.CASE["l_bound"], case.CASE["u_bound"])
    # the rung 0-1 spaces hold the same features: the reference's index of
    # each of the program's rows, by value
    x = fs.values_matrix()
    assert x.shape == space.values.shape
    near = np.abs(x[:, None, :] - space.values[None, :, :]).max(axis=2) \
        <= 1e-12 * np.abs(x).max(axis=1)[:, None]
    assert (near.sum(axis=1) == 1).all()
    ref_index = near.argmax(axis=1)
    assert sorted(ref_index) == list(range(len(x)))
    want = _reference_child_sets(space, ops)
    got = {name: [] for name in ops}
    for blk in fs.candidates:
        name = operators.OPS[blk.op_id].name
        arity = operators.OPS[blk.op_id].arity
        got[name] += [tuple(int(ref_index[r]) for r in (a, b)[:arity])
                      for a, b in zip(blk.child_a, blk.child_b)]
    for name in ops:
        commutative = operators.OP_BY_NAME[name].commutative
        canon = [tuple(sorted(c)) if commutative else c for c in got[name]]
        assert len(set(canon)) == len(canon), name        # each once
        assert set(canon) == {tuple(sorted(c)) if commutative else c
                              for c in want[name]}, name
    # the pairs of a rung-1 feature with a primary are there
    primaries = set(np.nonzero(space.rungs == 0)[0])
    assert any(a in primaries or b in primaries for a, b in got["mul"])
