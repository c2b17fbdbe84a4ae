"""The program's spans and counters (runtime/trace.py) over whole fits."""
import collections
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SissoRegressor
from repro.core import SissoConfig, SissoSolver, n_models
from repro.runtime import trace

#: every span of an estimator fit and its parent (stored features, so no
#: deferred candidates and no SIS block workers)
PARENTS = {
    "sisso.fit": None,
    "sisso.fc": "sisso.fit",
    "sisso.fc.enumerate": "sisso.fc",
    "sisso.fc.eval": "sisso.fc",
    "sisso.fc.admit": "sisso.fc",
    "sisso.sis": "sisso.fit",
    "sisso.l0": "sisso.fit",
    "sisso.l0.prepare": "sisso.l0",
    "sisso.l0.wait": "sisso.l0",
    "sisso.l0.merge": "sisso.l0",
    "sisso.l0.block": "sisso.l0",
    "sisso.l0.rescore": "sisso.l0.block",
    "sisso.models": "sisso.fit",
    "sisso.descriptor": "sisso.fit",
}
PHASES = ("fc", "sis", "l0", "models", "descriptor")


def _data(seed=0, s=40, p=6):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0.5, 3.0, (s, p))
    y = 2.0 * X[:, 1] * X[:, 3] - X[:, 2] + 0.5 + 0.01 * rng.normal(size=s)
    return X, y


def _estimator(backend, n_dim=3):
    return SissoRegressor(max_rung=1, n_dim=n_dim, n_sis=6, n_residual=3,
                          op_names=("add", "sub", "mul"), backend=backend,
                          l0_block=97)


def _names(fit):
    return collections.Counter(s.name for s in fit.trace.spans)


@pytest.fixture(scope="module", params=["jnp", "pallas"])
def fitted(request):
    X, y = _data()
    return _estimator(request.param).fit(X, y).fit_result_, request.param


def test_span_tree_of_an_estimator_fit(fitted):
    fit, backend = fitted
    names = _names(fit)
    want = set(PARENTS) - ({"sisso.l0.rescore"} if backend == "jnp" else set())
    assert set(names) == want
    for s in fit.trace.spans:
        assert s.parent == PARENTS[s.name], s
    for name in ("sisso.fit", "sisso.fc", "sisso.descriptor"):
        assert names[name] == 1
    for name in ("sisso.sis", "sisso.l0", "sisso.models", "sisso.l0.prepare"):
        assert names[name] == 3        # one per dimension
    # one wait, one worker block and one merge per ℓ0 block
    assert names["sisso.l0.block"] == names["sisso.l0.wait"] \
        == names["sisso.l0.merge"] > 3
    workers = {s.thread for s in fit.trace.spans
               if s.name in ("sisso.l0.block", "sisso.l0.rescore")}
    assert all(t.startswith("block-prefetch") for t in workers)
    main = {s.thread for s in fit.trace.spans} - workers
    assert main == {threading.current_thread().name}


def test_timings_are_the_spans_durations(fitted):
    fit, _ = fitted
    for key in ("fit",) + PHASES:
        span = trace.TIMED_SPANS[key]
        assert fit.timings[key] == pytest.approx(
            sum(s.seconds for s in fit.trace.spans if s.name == span))
    assert fit.timings["l0_wait"] == pytest.approx(
        fit.trace.seconds("sisso.l0.wait"))
    assert fit.timings["l0_wait"] <= fit.timings["l0"]
    assert sum(fit.timings[k] for k in PHASES) <= fit.timings["fit"]


def test_programs_counted_per_span(fitted):
    fit, _ = fitted
    programs = fit.stats["programs"]
    assert set(programs) <= set(PARENTS)
    for kinds in programs.values():
        assert set(kinds) == set(trace.PROGRAM_KINDS)
    assert sum(k["lowered"] for k in programs.values()) > 0


def test_fresh_jit_is_lowered_under_its_span():
    v = jnp.arange(7.0)
    jax.block_until_ready(v)
    with trace.collecting() as rec:
        with trace.span("test.outer"):
            with trace.span("test.inner"):
                out = jax.jit(lambda a: a * 3.0 + 1.0)(v)
                jax.block_until_ready(out)
    programs = rec.stats()["programs"]
    assert set(programs) == {"test.inner"}
    inner = programs["test.inner"]
    assert inner["lowered"] == 1
    assert inner["compiled"] + inner["cache_loads"] == 1


def test_spans_outside_a_fit_record_nothing():
    with trace.span("test.alone"):
        trace.count(("l0_paths", 3, "x"))
    with trace.collecting() as rec:
        pass
    assert [s.name for s in rec.spans] == ["sisso.fit"]
    assert rec.stats() == {"programs": {}, "l0_paths": {}}


def test_recent_fits_hold_the_fits_that_ran_to_their_end():
    with trace.collecting() as done:
        trace.count(("l0_paths", 3, "x"), 2)
    with pytest.raises(RuntimeError):
        with trace.collecting():
            raise RuntimeError("a fit that fails")
    with trace.collecting() as outer:
        with trace.collecting():           # a solver joining its estimator
            pass
    assert trace.recent_fits()[-2:] == [done, outer]
    assert trace.recent_fits()[-2].stats()["l0_paths"] == {3: {"x": 2}}
    for _ in range(trace.RECENT_FITS + 1):
        with trace.collecting():
            pass
    assert len(trace.recent_fits()) == trace.RECENT_FITS
    assert done not in trace.recent_fits()


def test_two_fits_in_two_threads_stay_apart():
    X, y = _data()
    X2, y2 = _data(seed=1)
    alone = [_estimator("pallas", 3).fit(X, y).fit_result_,
             _estimator("pallas", 2).fit(X2, y2).fit_result_]
    together = [None, None]

    def run(i, est, X_, y_):
        together[i] = est.fit(X_, y_).fit_result_

    threads = [
        threading.Thread(target=run, args=(0, _estimator("pallas", 3), X, y),
                         name="fit-a"),
        threading.Thread(target=run, args=(1, _estimator("pallas", 2), X2, y2),
                         name="fit-b"),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for a, b, own in zip(alone, together, ("fit-a", "fit-b")):
        assert _names(b) == _names(a)
        assert b.stats["l0_paths"] == a.stats["l0_paths"]
        threads_seen = {s.thread for s in b.trace.spans}
        assert own in threads_seen
        assert threads_seen - {own} and all(
            t.startswith("block-prefetch") for t in threads_seen - {own})
    assert _names(together[0])["sisso.l0"] == 3
    assert _names(together[1])["sisso.l0"] == 2
    assert together[0].stats["l0_paths"][3]


def test_l0_paths_of_a_reused_solver_are_per_fit():
    X, y = _data()
    cfg = SissoConfig(max_rung=1, n_dim=3, n_sis=6, n_residual=3,
                      op_names=("add", "sub", "mul"), backend="pallas",
                      l0_block=97)
    solver = SissoSolver(cfg)
    first = solver.fit(X.T, y, list("abcdef"))
    second = solver.fit(X.T, y, list("abcdef"))
    assert first.stats["l0_paths"] == second.stats["l0_paths"]
    # every ℓ0 block of this fit, and none of the first's, took one path
    paths = second.stats["l0_paths"]
    assert sum(sum(p.values()) for p in paths.values()) \
        == _names(second)["sisso.l0.block"]


def test_width3_blocks_count_their_enumeration_path(fitted):
    """Each width-3 block of the sweep over the 18-feature dim-3 subspace
    is enumerated on device in int32 and counted once; widths 1 and 2
    slice host arrays and count nothing; ``l0_paths`` still counts every
    block's scoring path where the backend records one (pallas)."""
    fit, backend = fitted
    enum, paths = fit.stats["l0_enum"], fit.stats["l0_paths"]
    n_blocks = -(-n_models(18, 3) // 97)
    assert enum == {3: {"device int32": n_blocks}}
    if backend == "jnp":
        assert paths == {}
        return
    assert sorted(paths) == [1, 2, 3]
    assert sum(paths[3].values()) == n_blocks
    assert sum(sum(p.values()) for p in paths.values()) \
        == _names(fit)["sisso.l0.block"]


def test_width3_blocks_count_their_kernel_panel_rows(fitted):
    """``l0_rows`` counts the Gram-gather kernel's panels over the lex
    sweep of the 18-feature dim-3 subspace in 97-tuple blocks: one row per
    (i, j) prefix run, plus one for each run a block end cuts; every tuple
    once; 128 lanes (m_pad) a row."""
    import itertools
    import math

    fit, backend = fitted
    if backend == "jnp":
        assert "l0_rows" not in fit.stats
        return
    m, n, block = 18, 3, 97
    tuples = list(itertools.combinations(range(m), n))
    cut = sum(tuples[r][:-1] == tuples[r - 1][:-1]
              for r in range(block, len(tuples), block))
    rows = math.comb(m - 1, n - 1) + cut
    assert fit.stats["l0_rows"] == {3: {
        "blocks": -(-len(tuples) // block), "rows": rows,
        "tuples": n_models(m, n), "lanes": rows * 128}}


def test_sharded_l0_programs_outlive_the_fit():
    """A second ``sharded:pallas`` fit finds the ℓ0 shard programs of the
    first: one per panel height, built once per process, whatever fit
    asks for it."""
    from repro.core.distributed import make_l0_topk_reduced_fn

    X, y = _data()
    _estimator("sharded:pallas").fit(X, y)
    before = make_l0_topk_reduced_fn.cache_info()
    fit = _estimator("sharded:pallas").fit(X, y).fit_result_
    after = make_l0_topk_reduced_fn.cache_info()
    blocks = fit.stats["l0_paths"][3]["sharded Gram-gather kernel"]
    assert blocks == fit.stats["l0_rows"][3]["blocks"] > 1
    assert after.misses == before.misses
    assert after.hits - before.hits == blocks


def test_enumeration_spans_one_per_operator_and_rung(fitted):
    """``sisso.fc.enumerate`` spans each operator's child enumeration at
    each rung (three operators, rung 1), summed as ``fc_enumerate``; a
    stored space screens no deferred block, so nothing waits on one and no
    SIS path or distribution counter is recorded."""
    fit, _ = fitted
    assert _names(fit)["sisso.fc.enumerate"] == 3
    assert fit.timings["fc_enumerate"] == pytest.approx(
        fit.trace.seconds("sisso.fc.enumerate"))
    assert 0 <= fit.timings["fc_enumerate"] <= fit.timings["fc"]
    assert "sis_wait" not in fit.timings
    assert "sis_paths" not in fit.stats and "sharded" not in fit.stats


OTF_PATHS = {"jnp": "evaluate then screen", "pallas": "fused kernel",
             "sharded:pallas": "fused kernel in shard_map"}


@pytest.fixture(scope="module", params=sorted(OTF_PATHS))
def otf_fitted(request):
    X, y = _data()
    est = SissoRegressor(max_rung=1, n_dim=2, n_sis=6, n_residual=3,
                         op_names=("add", "sub", "mul"),
                         on_the_fly_last_rung=True, backend=request.param,
                         l0_block=97)
    return est.fit(X, y).fit_result_, request.param


def test_deferred_blocks_count_their_scoring_path(otf_fitted):
    """Every deferred-candidate block is counted once, under the path that
    scored it, and the screen's waits on the block workers sum to
    ``sis_wait``."""
    fit, backend = otf_fitted
    names = _names(fit)
    assert names["sisso.sis.block"] == names["sisso.sis.wait"] >= 2 * 3
    assert fit.stats["sis_paths"] == {
        OTF_PATHS[backend]: names["sisso.sis.block"]}
    assert fit.timings["sis_wait"] == pytest.approx(
        fit.trace.seconds("sisso.sis.wait"))
    assert 0 <= fit.timings["sis_wait"] <= fit.timings["sis"]


def test_sharded_merges_are_counted_from_static_shapes(otf_fitted):
    """A distributed fit records its devices, one k-sized merge per
    screened block and per ℓ0 block, and the winner rows each merge
    gathered from every shard; other engines record none."""
    fit, backend = otf_fitted
    if backend != "sharded:pallas":
        assert "sharded" not in fit.stats
        return
    sharded, names = fit.stats["sharded"], _names(fit)
    devices = jax.device_count()
    assert sharded["devices"] == devices
    assert set(sharded["merges"]) == set(sharded["gathered_rows"]) \
        == {"sis", "l0"}
    assert sharded["merges"]["l0"] == names["sisso.l0.block"]
    # deferred blocks, plus one block of stored features per screen
    assert sharded["merges"]["sis"] > names["sisso.sis.block"]
    for phase, merges in sharded["merges"].items():
        rows = sharded["gathered_rows"][phase]
        assert rows % devices == 0 and merges <= rows


def test_profiler_trace_shows_the_spans(tmp_path):
    X, y = _data()
    est = _estimator("jnp", 2)
    est.fit(X, y)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        est.fit(X, y)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    data = jax.profiler.ProfileData.from_file(files[0])
    lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sisso."):
                    lines.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    (fit_line, f0, f1), = lines["sisso.fit"]
    for name in ("sisso.fc", "sisso.l0"):
        assert lines[name] and all(ln == fit_line for ln, _, _ in lines[name])
    assert lines["sisso.l0.block"]
    for name in ("sisso.fc", "sisso.l0", "sisso.l0.block"):
        assert all(f0 <= a <= b <= f1 for _, a, b in lines[name])
    assert all(ln == "block-prefetch" for ln, _, _ in lines["sisso.l0.block"])
