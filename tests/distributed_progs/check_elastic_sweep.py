"""Elastic fault-tolerant ℓ0 sweep: coordinator + N workers under injected
faults, checked bit-identical against the fault-free single-process run.

Topology: one coordinator process (this one) spawns ``N_WORKERS`` worker
subprocesses speaking a line protocol over stdin/stdout::

    coordinator -> worker:  SCORE <bi>         QUIT
    worker -> coordinator:  READY              RESULT <bi> <panel-json>

Each process regenerates the identical dataset from a fixed seed (nothing
is shipped but block indices and top-k panels — the real multi-host
deployment shape).  Blocks are rank ranges of the width-4 lexicographic
tuple space; a worker scores a block with the reference engine and returns
its stable-argsort top-``N_KEEP`` panel, exactly the per-block panel
``l0_search`` merges.

Injected faults, and what must survive them:

* worker 0 runs under ``REPRO_FAULTS=worker.tick:kill@1`` — it dies with
  ``os._exit(137)`` on its first block, mid-lease (every worker is handed
  its first block at once, so the kill cannot miss however the load skews
  the workers' speeds).  The coordinator sees
  EOF, releases its leases (``LeaseTable.release_worker``) and the block
  *reissues* to a surviving worker.
* the coordinator's 2nd journal publication is torn mid-JSON
  (``journal.write:torn@2``), then the coordinator "crashes": all
  in-memory state is discarded and rebuilt via ``restore_elastic()``,
  which must fall back to the rotated ``.bak`` generation.  Resume
  re-scores only blocks absent from the restored panel set; acked blocks
  are never reissued.

Final check: ``merge_block_results`` over the acked panels equals — to the
bit — the fault-free single-process ``l0_search`` top-k.
"""
import json
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading

import numpy as np

from repro.core.l0 import TupleEnumerator, l0_search
from repro.core.sis import TaskLayout
from repro.engine import get_engine
from repro.runtime import FaultPlan, LeaseTable, WorkJournal, faults
from repro.runtime.journal import merge_block_results

M = 12           # SIS subspace size -> C(12, 4) = 495 tuples
N_DIM = 4
BLOCK = 32       # -> 16 blocks
N_KEEP = 7
S = 48
SEED = 7
N_WORKERS = 3


def make_data():
    rng = np.random.default_rng(SEED)
    x = rng.uniform(0.5, 3.0, (M, S))
    y = 1.5 * x[2] - 0.7 * x[5] * x[9] + rng.normal(0, 0.05, S)
    return x, y, TaskLayout.single(S)


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def worker_main(rank: int) -> None:
    x, y, layout = make_data()
    eng = get_engine("reference")
    prob = eng.prepare_l0(x, y, layout, method="gram", dtype=np.float64)
    enum = TupleEnumerator(M, N_DIM, BLOCK)
    sys.stdout.write("READY\n")
    sys.stdout.flush()
    for line in sys.stdin:
        parts = line.split()
        if not parts or parts[0] == "QUIT":
            break
        assert parts[0] == "SCORE", parts
        bi = int(parts[1])
        # fault site: REPRO_FAULTS=worker.tick:kill@1 makes rank 0 die
        # here (os._exit) holding its lease — the preemption under test
        faults.check("worker.tick")
        tuples = np.asarray(enum.block_tuples(bi))
        sses = np.asarray(eng.l0_scores(prob, tuples, n_keep=N_KEEP))
        # the exact per-block panel l0_search merges: stable argsort so
        # objective ties resolve identically
        part = np.argsort(sses, kind="stable")[: min(N_KEEP, len(sses))]
        panel = {"sse": sses[part].tolist(),
                 "tuples": tuples[part].astype(np.int64).tolist()}
        sys.stdout.write(f"RESULT {bi} {json.dumps(panel)}\n")
        sys.stdout.flush()


# ---------------------------------------------------------------------------
# coordinator
# ---------------------------------------------------------------------------

def _reader(rank: int, proc, events: "queue.Queue") -> None:
    for line in proc.stdout:
        parts = line.split(None, 2)
        if not parts:
            continue
        if parts[0] == "READY":
            events.put(("ready", rank, None, None))
        elif parts[0] == "RESULT":
            events.put(("result", rank, int(parts[1]), json.loads(parts[2])))
    events.put(("dead", rank, None, None))


def coordinator_main() -> None:
    x, y, layout = make_data()

    # fault-free oracle: the single-process sweep the elastic run must
    # reproduce bit-for-bit
    ref = l0_search(x, y, layout, n_dim=N_DIM, n_keep=N_KEEP, block=BLOCK,
                    engine="reference")
    n_blocks = TupleEnumerator(M, N_DIM, BLOCK).n_blocks
    assert n_blocks >= 10, n_blocks  # enough blocks to kill a worker mid-sweep

    # coordinator-side fault: tear the 2nd journal publication mid-JSON
    plan = FaultPlan().add("journal.write", "torn", at=2)
    faults.install(plan)

    events: "queue.Queue" = queue.Queue()
    procs, alive, idle = {}, set(), set()
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "src")))
    for rank in range(N_WORKERS):
        wenv = dict(env)
        if rank == 0:
            wenv["REPRO_FAULTS"] = "worker.tick:kill@1"
        procs[rank] = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "worker", str(rank)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=sys.stderr, text=True, env=wenv)
        threading.Thread(target=_reader, args=(rank, procs[rank], events),
                         daemon=True).start()

    journal_dir = tempfile.mkdtemp(prefix="elastic-journal-")
    path = os.path.join(journal_dir, "journal.json")
    journal = WorkJournal(path)
    journal.clear()
    table = LeaseTable(n_blocks, ttl=300.0)
    results = {}

    saw_kill = False
    crashed = False
    acked_at_restore = None
    post_crash_issued = set()

    def dispatch(rank: int) -> None:
        unit = table.next_unit(f"w{rank}")
        if unit is None:
            idle.add(rank)
            return
        idle.discard(rank)
        if crashed:
            post_crash_issued.add(unit)
        procs[rank].stdin.write(f"SCORE {unit}\n")
        procs[rank].stdin.flush()

    while not table.done:
        kind, rank, bi, panel = events.get(timeout=120)
        if kind == "ready":
            alive.add(rank)
            # start every worker together: a worker that came up late could
            # otherwise find the sweep done before it gets a block
            if len(alive) == N_WORKERS:
                for r in sorted(alive):
                    dispatch(r)
        elif kind == "dead":
            if rank in alive:
                alive.discard(rank)
                idle.discard(rank)
                saw_kill = True
                table.release_worker(f"w{rank}")
        elif kind == "result":
            if table.ack(bi, f"w{rank}"):
                results[bi] = (np.asarray(panel["sse"], np.float64),
                               np.asarray(panel["tuples"], np.int64))
            journal.record_elastic(table, results)
            if not crashed and plan.fired_at("journal.write", "torn"):
                # --- simulated coordinator crash -----------------------
                # forget everything; reload from disk.  The current file
                # is torn, so restore must fall back to the .bak.
                crashed = True
                journal = WorkJournal(path)
                table, results = journal.restore_elastic()
                assert journal.recovered_from_bak, "expected .bak fallback"
                acked_at_restore = set(table.acked)
                # nothing is known about in-flight work after a restart:
                # expire every outstanding lease so unacked blocks reissue
                table.expire_all()
                print("elastic: torn journal -> .bak recovery: OK")
            if rank in alive:
                dispatch(rank)
        # newly issuable units (released by a death / expired by the
        # crash) go to whoever is idle
        for r in sorted(idle & alive):
            dispatch(r)

    for rank in sorted(alive):
        procs[rank].stdin.write("QUIT\n")
        procs[rank].stdin.flush()
    for proc in procs.values():
        proc.wait(timeout=60)
    assert procs[0].returncode == faults.KILL_EXIT_CODE, procs[0].returncode

    assert saw_kill, "worker 0 should have been killed mid-sweep"
    assert table.reissues >= 1, table.reissues
    print("elastic: worker kill + lease reissue: OK")

    assert crashed and acked_at_restore is not None
    assert not (post_crash_issued & acked_at_restore), (
        "acked blocks must not be re-scored after restore: "
        f"{sorted(post_crash_issued & acked_at_restore)}")
    print("elastic: no re-issue of acked blocks: OK")

    assert set(results) == set(range(n_blocks))
    sse, tuples = merge_block_results(results, N_KEEP)
    np.testing.assert_array_equal(sse, ref.sses)
    np.testing.assert_array_equal(tuples, ref.tuples)
    print("elastic: final top-k bit-identical to fault-free l0_search: OK")
    shutil.rmtree(journal_dir, ignore_errors=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "worker":
        worker_main(int(sys.argv[2]))
    else:
        coordinator_main()
