"""Soak: concurrent submitters + auto-flusher + rule reloads + mesh
toggles, sustained for SENTINEL_SOAK_SEC (default 90s) of wall time.

Round-3 verdict #8: the auto-flusher/lock redesigns are exactly where
a rare interleaving bug would hide. The invariants checked are the
strong ones a race would break:

* liveness — no thread dies, every submitted op gets a verdict;
* accounting — for every resource, the engine's own window tensors
  agree exactly with the tally of verdicts handed back to callers
  (lost/double-counted rows under lock handoffs would skew one side);
* conservation — an unlimited resource admits everything submitted;
* memory — RSS stops growing once warm (no leak per flush).

The clock is a ManualClock advanced by a dedicated thread, so the
whole soak stays inside one minute window and the accounting check is
exact equality, not a rate estimate. Reference analog: the reference's
concurrency safety is by construction (CAS/LongAdder); this is the
empirical equivalent for the batched engine.
"""

import os
import threading
import time

import numpy as np
import pytest

import sentinel_tpu as st

pytestmark = pytest.mark.slow

SOAK_SEC = float(os.environ.get("SENTINEL_SOAK_SEC", "90"))


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / 1e6


def test_soak_concurrent_engine(manual_clock, engine):
    rules = [
        st.FlowRule("unlimited", count=1e9),
        st.FlowRule("limited", count=40),
        st.FlowRule("threads", grade=0, count=64),
    ]
    engine.set_flow_rules(rules)
    engine.start_auto_flush(interval_ms=2)
    manual_clock.set_ms(1000)

    stop = threading.Event()
    errors = []
    lock = threading.Lock()
    tallies = {"unlimited": 0, "limited": 0, "threads": 0}
    submitted = {"unlimited": 0, "limited": 0, "threads": 0}
    undecided = []

    def submitter(i):
        rng = np.random.default_rng(i)
        try:
            while not stop.is_set():
                res = ("unlimited", "limited", "threads")[int(rng.integers(0, 3))]
                if rng.random() < 0.5:
                    n = int(rng.integers(8, 64))
                    g = engine.submit_bulk(res, n)
                    t0 = time.time()
                    while g.admitted is None and time.time() - t0 < 10:
                        time.sleep(0.001)
                    if g.admitted is None:
                        # A mesh toggle's recompile can stall the
                        # auto-flusher well past 10s on small hosts; a
                        # synchronous flush settles it (and would hang
                        # here on a real deadlock, failing the join
                        # check below).
                        engine.flush()
                    if g.admitted is None:
                        undecided.append((res, "bulk"))
                        continue
                    adm = int(g.admitted_count)
                    with lock:
                        submitted[res] += n
                        tallies[res] += adm
                    if res == "threads" and adm:
                        engine.submit_exit_bulk(
                            g.rows, adm, rt=3, resource=res
                        )
                else:
                    ops = engine.submit_many(
                        [{"resource": res} for _ in range(int(rng.integers(1, 12)))]
                    )
                    engine.flush()
                    n_adm = 0
                    for op in ops:
                        if op.verdict is None:
                            undecided.append((res, "single"))
                        elif op.verdict.admitted:
                            n_adm += 1
                            if res == "threads":
                                engine.submit_exit(op.rows, rt=3, resource=res)
                    with lock:
                        submitted[res] += len(ops)
                        tallies[res] += n_adm
        except Exception as e:  # pragma: no cover - the failure path
            errors.append(e)

    def clock_advancer():
        # ~55s of virtual time over the whole soak — stays inside the
        # minute window so minute-window totals hold every event.
        try:
            step_ms = max(1, int(55_000 * 0.05 / max(SOAK_SEC, 1)))
            while not stop.is_set():
                time.sleep(0.05)
                manual_clock.advance(step_ms)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    t_start = time.time()

    def churner():
        # Rule reloads while traffic flows; mesh toggles confined to
        # the first half — every enable_mesh builds fresh shard_map
        # closures whose pjit compiles legitimately grow the executable
        # cache, and the steady-state RSS check below must measure
        # flushing, not compiles. Toggles need an 8-device mesh.
        from sentinel_tpu.parallel import mesh_unavailable_reason

        mesh_ok = mesh_unavailable_reason(8) is None
        try:
            toggles = 0
            while not stop.is_set():
                time.sleep(max(SOAK_SEC / 12, 1.0))
                engine.set_flow_rules(rules)
                if (
                    mesh_ok
                    and toggles < 2
                    and SOAK_SEC >= 60
                    and time.time() - t_start < SOAK_SEC * 0.4
                ):
                    engine.enable_mesh(8)
                    time.sleep(max(SOAK_SEC / 12, 1.0))
                    engine.disable_mesh()
                    toggles += 1
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=submitter, args=(i,)) for i in range(4)]
    threads += [threading.Thread(target=clock_advancer), threading.Thread(target=churner)]
    for t in threads:
        t.start()

    time.sleep(SOAK_SEC * 0.7)  # past the toggle window + its compiles
    rss_warm = _rss_mb()
    time.sleep(SOAK_SEC * 0.3)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads), "a soak thread deadlocked"
    engine.flush()
    engine.stop_auto_flush()
    rss_end = _rss_mb()

    assert not errors, errors
    assert not undecided, f"{len(undecided)} ops never decided: {undecided[:5]}"
    # Scale with duration: early iterations are compile-dominated on
    # small hosts (every fresh batch-size bucket jits once).
    assert sum(submitted.values()) > 8 * SOAK_SEC, "soak produced too little traffic"

    # Unlimited resource: everything admitted.
    assert tallies["unlimited"] == submitted["unlimited"]

    # The engine's own windows agree with the verdicts we were handed.
    for res in tallies:
        stats = engine.cluster_node_stats(res, flush=False)
        total = stats["total_pass_minute"]
        assert total == tallies[res], (
            f"{res}: window says {total}, verdict tally {tallies[res]}"
        )

    # No leak once warm: flushes must not accrete host memory.
    assert rss_end - rss_warm < 300, (
        f"RSS grew {rss_end - rss_warm:.0f} MB after warmup"
    )
