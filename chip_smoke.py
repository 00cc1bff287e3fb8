"""Drive the admission engine once on one TPU chip at north-star table size.

The deployment (all data generated from ``--seed``):

* source: ``BASELINE.json`` ``north_star`` (1M FlowRules, 100k+ buffered
  entries per flush) with its configs #1 (upstream sentinel-demo-basic
  FlowQpsDemo: QPS=20 on one resource), #3 (one hot-param rule over
  100,000 distinct keys) and #4 (slow-ratio + exception-ratio
  DegradeRules);
* 1,048,576 FlowRules: 1/8 RATE_LIMITER, 1/16 WARM_UP, the rest QPS
  DEFAULT, so the mixed slot chain compiles;
* traffic over 6,000 distinct resources, the upstream per-process cap
  (``MAX_SLOT_CHAIN_SIZE``), in flushes of 131,072 entries (the default
  ``flush.max.batch``) at pipeline depth 0 and 2, with exits that carry
  rt and errors;
* hot-param keys drawn Zipfian (theta 0.99).  The rule's duration is 25 s,
  which sizes its value table (4000 x duration) to hold all 100,000 keys;
* failover armed, as a supervised deployment runs it: its watchdog bounds
  every dispatch and fetch, cold compiles included, and any trip or
  policy-served verdict fails the smoke.

Everything goes through the public surface: the rule managers, ``st.entry``
and ``Engine.submit_bulk`` / ``submit_exit_bulk`` / ``flush``.  The result is
checked verdict for verdict against ``sentinel_tpu.testing.oracle`` on a
sample of resources, plus three deterministic pins.  Flush times printed here
are host-clock smoke figures, not device metrics.

``--chips 4`` runs only the mesh path: the same seeded stream through
``Engine.enable_mesh(4)`` and through a one-device Engine, verdict for verdict,
plus ``__graft_entry__.dryrun_multichip``'s conserved-budget pins.

The phase functions take a :class:`Scale`, so tier-1 tests run them at a
tiny size on the CPU; only :func:`main` insists on a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

ZIPF_THETA = 0.99
PARAM_COUNT = 10  # tokens per key per duration
PARAM_DURATION_S = 25  # 4000 * 25 = 100,000 cached values: no LRU eviction
DEMO_COUNT = 20  # FlowQpsDemo's QPS rule


@dataclass(frozen=True)
class Scale:
    rules: int  # FlowRules loaded, one per resource name
    resources: int  # distinct resources driven
    degrade: int  # resources with a DegradeRule (half slow-ratio)
    param_keys: int  # distinct hot-param values
    batch: int  # entries per flush
    param_share: int  # entries per flush on the hot-param resource
    exits: int  # completions per flush (a fixed count: one padded shape)
    flushes: int  # flushes at each pipeline depth
    sample: int  # resources replayed through the oracle


FULL = Scale(rules=1 << 20, resources=6000, degrade=1024, param_keys=100_000,
             batch=1 << 17, param_share=1 << 14, exits=1 << 14, flushes=4,
             sample=64)
# The mesh comparison runs the same tables at a quarter of the batch: each
# of the four chips then holds 8,192 entries of every flush.
MESH = Scale(rules=1 << 20, resources=6000, degrade=1024, param_keys=100_000,
             batch=1 << 15, param_share=1 << 12, exits=1 << 12, flushes=3,
             sample=0)
TINY = Scale(rules=512, resources=48, degrade=8, param_keys=500, batch=1024,
             param_share=128, exits=128, flushes=3, sample=16)

DEMO_RES = "res-0"  # resource 0 carries the FlowQpsDemo rule
PARAM_RES = "res-1"  # resource 1 carries the hot-param rule


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# rules and traffic
# ---------------------------------------------------------------------------
@dataclass
class World:
    scale: Scale
    flow: list  # FlowRule per resource index (rules[i].resource == res-i)
    degrade: Dict[int, object]  # resource index -> DegradeRule
    param: object  # the one ParamFlowRule
    sizes: np.ndarray  # entries per flush for resources 0..resources-1
    keys: List[str]  # the hot-param key space


def build_world(scale: Scale, seed: int) -> World:
    import sentinel_tpu as st
    from sentinel_tpu.models import constants as C

    rng = np.random.default_rng(seed)
    n = scale.rules
    kind = np.arange(n) % 16  # 0,1: rate limiter; 2: warm-up; else default
    counts = rng.integers(5, 41, n)
    maxq = rng.integers(0, 501, n)
    warm = rng.integers(2, 11, n)
    rl, wu = C.CONTROL_BEHAVIOR_RATE_LIMITER, C.CONTROL_BEHAVIOR_WARM_UP
    flow = []
    for i in range(n):
        k = kind[i]
        if k < 2:
            r = st.FlowRule(f"res-{i}", count=float(counts[i]),
                            control_behavior=rl,
                            max_queueing_time_ms=int(maxq[i]))
        elif k == 2:
            r = st.FlowRule(f"res-{i}", count=float(counts[i] + 20),
                            control_behavior=wu,
                            warm_up_period_sec=int(warm[i]))
        else:
            r = st.FlowRule(f"res-{i}", count=float(counts[i]))
        flow.append(r)
    flow[0] = st.FlowRule(DEMO_RES, count=float(DEMO_COUNT))
    flow[1] = st.FlowRule(PARAM_RES, count=1e9)  # the param rule decides
    degrade = {}
    picks = rng.choice(np.arange(2, scale.resources), scale.degrade,
                       replace=False)
    for j, i in enumerate(sorted(int(p) for p in picks)):
        if j % 2 == 0:
            degrade[i] = st.DegradeRule(
                f"res-{i}", grade=C.DEGRADE_GRADE_RT, count=80.0,
                slow_ratio_threshold=0.5, min_request_amount=5, time_window=1,
            )
        else:
            degrade[i] = st.DegradeRule(
                f"res-{i}", grade=C.DEGRADE_GRADE_EXCEPTION_RATIO, count=0.3,
                min_request_amount=5, time_window=1,
            )
    param = st.ParamFlowRule(PARAM_RES, param_idx=0, count=PARAM_COUNT,
                             duration_in_sec=PARAM_DURATION_S)
    rest = scale.batch - scale.param_share
    others = scale.resources - 1
    sizes = np.ones(scale.resources, dtype=np.int64)
    sizes[1] = scale.param_share
    sizes[np.arange(scale.resources) != 1] += rng.multinomial(
        rest - others, np.full(others, 1.0 / others)
    )
    assert int(sizes.sum()) == scale.batch
    keys = [f"user-{k}" for k in range(scale.param_keys)]
    return World(scale, flow, degrade, param, sizes, keys)


def load_world(world: World) -> float:
    """Load every rule through the public rule managers; returns host
    seconds."""
    import sentinel_tpu as st

    t0 = time.perf_counter()
    st.flow_rule_manager.load_rules(world.flow)
    st.degrade_rule_manager.load_rules(list(world.degrade.values()))
    st.param_flow_rule_manager.load_rules([world.param])
    return time.perf_counter() - t0


def _zipf_p(n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** ZIPF_THETA
    return p / p.sum()


@dataclass
class FlushRecord:
    t: int
    depth: int
    groups: list  # (resource index, BulkOp)
    param_keys: np.ndarray  # key indices of the hot-param group
    exits: list  # (resource index, rt[n], err[n])
    host_ms: float


def run_traffic(engine, world: World, clock, depth: int, rng,
                records: List[FlushRecord]) -> None:
    """``scale.flushes`` flushes of ``scale.batch`` entries at pipeline
    ``depth``.  Each flush first completes up to ``scale.exits`` admitted
    entries of the flush before it (breaker resources first), with random
    rt and errors."""
    from sentinel_tpu.rules.param_table import ArgsColumns

    scale = world.scale
    engine.set_depth(depth)
    pz = _zipf_p(scale.param_keys)
    order = sorted(world.degrade) + [
        i for i in range(scale.resources) if i not in world.degrade
    ]
    for _ in range(scale.flushes):
        clock.advance(int(rng.integers(150, 600)))
        t = clock.now_ms()
        t0 = time.perf_counter()
        exits = []
        if records:
            prev = dict(records[-1].groups)
            budget = scale.exits
            for i in order:
                g = prev.get(i)
                if g is None or budget == 0:
                    continue
                n = min(g.admitted_count, budget)
                if n == 0:
                    continue
                rt = rng.integers(1, 160, n).astype(np.int32)
                err = (rng.random(n) < 0.2).astype(np.int32)
                engine.submit_exit_bulk(g.rows, n, rt=rt, err=err,
                                        resource=g.resource)
                exits.append((i, rt, err))
                budget -= n
        kidx = rng.choice(scale.param_keys, scale.param_share, p=pz)
        groups = []
        for i in range(scale.resources):
            n = int(world.sizes[i])
            args = None
            if i == 1:
                args = ArgsColumns(n, {0: [world.keys[k] for k in kidx]})
            g = engine.submit_bulk(f"res-{i}", n, args_column=args)
            if g is None:
                raise RuntimeError(f"res-{i} passed through (over the cap?)")
            groups.append((i, g))
        engine.flush()
        records.append(FlushRecord(t, depth, groups, kidx, exits,
                                   (time.perf_counter() - t0) * 1e3))
    engine.drain()


def run_flow_qps_demo(clock, windows: int = 3, offered: int = 30) -> List[int]:
    """FlowQpsDemo's sync path: ``st.entry`` on the QPS=20 rule, one
    offered burst per 1 s window; returns the passes per window."""
    import sentinel_tpu as st

    st.get_engine().set_depth(0)
    passes = []
    base = clock.now_ms() + 60_000  # clear of the bulk traffic's windows
    for w in range(windows):
        clock.set_ms(base + 1500 * w)
        ok = 0
        for _ in range(offered):
            try:
                with st.entry(DEMO_RES):
                    ok += 1
            except st.FlowBlockError:
                pass
        passes.append(ok)
    return passes


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
class _Ref:
    """One resource under ``testing/oracle``: flow controller, optional
    breaker, optional per-key param buckets."""

    def __init__(self, flow, drule, prule) -> None:
        from sentinel_tpu.models import constants as C
        from sentinel_tpu.testing import oracle as O

        self.node = O.OracleNode()
        cb = flow.control_behavior
        if cb == C.CONTROL_BEHAVIOR_RATE_LIMITER:
            self.ctrl = O.OracleRateLimiter(flow.count, flow.max_queueing_time_ms)
            self.decide = lambda t: self.ctrl.can_pass(t)
        elif cb == C.CONTROL_BEHAVIOR_WARM_UP:
            self.ctrl = O.OracleWarmUp(flow.count, flow.warm_up_period_sec)
            self.decide = lambda t: (self.ctrl.can_pass(self.node, t), 0)
        else:
            self.ctrl = O.OracleDefaultController(flow.count, grade=1)
            self.decide = lambda t: (self.ctrl.can_pass(self.node, t), 0)
        self.breaker = None
        if drule is not None:
            self.breaker = O.OracleCircuitBreaker(
                grade=drule.grade, count=drule.count,
                time_window_sec=drule.time_window,
                min_request=drule.min_request_amount,
                slow_ratio=drule.slow_ratio_threshold,
                stat_interval_ms=drule.stat_interval_ms,
            )
        self.prule = prule
        self.buckets: Dict[int, object] = {}

    def entry(self, t: int, key: Optional[int]) -> tuple:
        from sentinel_tpu.testing import oracle as O

        if self.prule is not None:  # ParamFlowSlot runs before FlowSlot
            b = self.buckets.get(key)
            if b is None:
                r = self.prule
                b = O.OracleParamBucket(int(r.count), int(r.burst_count),
                                        int(r.duration_in_sec) * 1000)
                self.buckets[key] = b
            if not b.check(t):
                self.node.add_block(t, 1)
                return False, 0
        ok, wait = self.decide(t)
        if ok and self.breaker is not None and not self.breaker.try_pass(t):
            ok, wait = False, 0
        if ok:
            self.node.cur_thread_num += 1
            self.node.add_pass(t, 1)
        else:
            self.node.add_block(t, 1)
        return ok, wait

    def exit(self, t: int, rt: int, err: int) -> None:
        if self.breaker is not None:
            self.breaker.on_complete(t, rt, error=bool(err))
        self.node.cur_thread_num -= 1
        self.node.add_rt_and_success(t, rt, 1)


def sample_resources(world: World, seed: int) -> List[int]:
    """Resources 0 and 1, then an even draw over rate limiter, warm-up,
    breaker and plain QPS resources."""
    from sentinel_tpu.models import constants as C

    rng = np.random.default_rng(seed + 1)
    cats: Dict[str, List[int]] = {"rl": [], "wu": [], "brk": [], "qps": []}
    for i in range(2, world.scale.resources):
        cb = world.flow[i].control_behavior
        if cb == C.CONTROL_BEHAVIOR_RATE_LIMITER:
            cats["rl"].append(i)
        elif cb == C.CONTROL_BEHAVIOR_WARM_UP:
            cats["wu"].append(i)
        elif i in world.degrade:
            cats["brk"].append(i)
        else:
            cats["qps"].append(i)
    per = max(1, (world.scale.sample - 2) // len(cats))
    out = [0, 1]
    for ids in cats.values():
        take = min(per, len(ids))
        out += [int(i) for i in rng.choice(ids, take, replace=False)]
    return sorted(out)


def replay_oracle(world: World, records: List[FlushRecord],
                  sample: List[int]) -> tuple:
    """Replay every op on ``sample`` in the engine's documented flush order
    (exits first, then entries by arrival).  Returns (ops checked,
    mismatching verdicts)."""
    refs = {
        i: _Ref(world.flow[i], world.degrade.get(i),
                world.param if i == 1 else None)
        for i in sample
    }
    checked = bad = 0
    for rec in records:
        for i, rt, err in rec.exits:
            ref = refs.get(i)
            if ref is not None:
                for r, e in zip(rt.tolist(), err.tolist()):
                    ref.exit(rec.t, r, e)
        for i, g in rec.groups:
            ref = refs.get(i)
            if ref is None:
                continue
            adm, wait = g.admitted, g.wait_ms
            keys = rec.param_keys if i == 1 else [None] * g.n
            for j in range(g.n):
                ok, w = ref.entry(rec.t, keys[j])
                checked += 1
                # A blocked verdict's wait is never slept on: compare it only
                # where the entry was admitted.
                bad += int(ok != bool(adm[j]) or (ok and w != int(wait[j])))
    return checked, bad


def check_pins(world: World, records: List[FlushRecord],
               demo_passes: List[int]) -> List[str]:
    """Deterministic pins over ALL traffic; returns the failures."""
    from sentinel_tpu.models import constants as C

    fails = []
    if demo_passes != [DEMO_COUNT] * len(demo_passes):
        fails.append(f"FlowQpsDemo passes per window {demo_passes}")
    # Pacer spacing: in one flush at one ts, a rate limiter's admitted
    # waits step by exactly its cost and never exceed max queueing time.
    for rec in records:
        for i, g in rec.groups:
            r = world.flow[i]
            if r.control_behavior != C.CONTROL_BEHAVIOR_RATE_LIMITER:
                continue
            w = g.wait_ms[g.admitted]
            cost = int(1000.0 / r.count + 0.5)
            if w.size and (np.any(np.diff(w) != cost)
                           or int(w.max()) > r.max_queueing_time_ms):
                fails.append(f"pacer spacing res-{i} waits {w.tolist()}")
                break
    # Param bucket: the whole run sits inside one duration, so every key
    # admits exactly min(offered, count).
    offered = np.zeros(world.scale.param_keys, dtype=np.int64)
    admitted = np.zeros_like(offered)
    for rec in records:
        g = dict(rec.groups)[1]
        np.add.at(offered, rec.param_keys, 1)
        np.add.at(admitted, rec.param_keys, g.admitted.astype(np.int64))
    want = np.minimum(offered, PARAM_COUNT)
    if np.any(admitted != want):
        n = int(np.count_nonzero(admitted != want))
        fails.append(f"param bucket: {n} keys admit != min(offered, count)")
    return fails


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------
class CompileLog:
    """Counts XLA compiles and their seconds, and persistent-cache hits,
    from ``jax.monitoring`` events."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.each: List[tuple] = []  # (seconds, jit name) per compile

        def on_duration(event, secs, fun_name="?", **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs
                self.each.append((secs, fun_name))

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compiles: {self.compiles}  compile_s: {self.seconds:.3f}  "
                f"persistent_cache_hits: {self.cache_hits}")

    def longest(self, n: int = 6) -> str:
        top = sorted(self.each, reverse=True)[:n]
        return "  ".join(f"{name}={secs:.3f}" for secs, name in top)


def policy_served(engine) -> int:
    """Verdicts the failover layer served from host policy instead of the
    device (degraded admits and blocks, quarantined records)."""
    c = engine.failover.counters
    return (c["degraded_admits"] + c["degraded_blocks"]
            + c["quarantined_records"])


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def run_single(scale: Scale, seed: int) -> dict:
    """The one-chip path, failover armed as a supervised deployment runs
    it (its watchdog bounds every dispatch and fetch, cold compiles
    included); returns the figures ``main`` prints and checks."""
    import sentinel_tpu as st
    from sentinel_tpu.core import api
    from sentinel_tpu.utils.clock import ManualClock, set_default_clock
    from sentinel_tpu.utils.config import config

    world = build_world(scale, seed)
    clock = ManualClock(start_ms=10_000)
    prev_clock = set_default_clock(clock)
    prev_armed = config.get(config.FAILOVER_ENABLED)
    config.set(config.FAILOVER_ENABLED, "true")
    try:
        engine = api.reset(clock=clock)
        load_s = load_world(world)
        rng = np.random.default_rng(seed + 2)
        records: List[FlushRecord] = []
        run_traffic(engine, world, clock, 0, rng, records)
        run_traffic(engine, world, clock, 2, rng, records)
        demo = run_flow_qps_demo(clock)
        sample = sample_resources(world, seed)[: scale.sample]
        checked, bad = replay_oracle(world, records, sample)
        return {
            "load_s": load_s,
            "rules": len(st.flow_rule_manager.get_rules()),
            "degrade_rules": len(st.degrade_rule_manager.get_rules()),
            "resources": engine.nodes._n_resources,
            "stats_rows": int(engine.stats.n_rows),
            "flushes": {d: sum(r.depth == d for r in records) for d in (0, 2)},
            "host_ms": {
                d: float(np.median([r.host_ms for r in records if r.depth == d]))
                for d in (0, 2)
            },
            "admitted": int(sum(g.admitted_count for r in records
                                for _, g in r.groups)),
            "entries": int(sum(g.n for r in records for _, g in r.groups)),
            "exits": int(sum(len(rt) for r in records for _, rt, _ in r.exits)),
            "demo": demo,
            "sample": len(sample),
            "checked": checked,
            "mismatches": bad,
            "pin_failures": check_pins(world, records, demo),
            "armed": engine.failover.armed,
            "health": engine.failover.state,
            "trips": engine.failover.counters["trips"],
            "policy_served": policy_served(engine),
        }
    finally:
        config.set(config.FAILOVER_ENABLED, prev_armed)
        set_default_clock(prev_clock)


def _single_ok(r: dict, scale: Scale) -> List[str]:
    fails = list(r["pin_failures"])
    if r["mismatches"]:
        fails.append(f"{r['mismatches']} oracle mismatches")
    if r["policy_served"]:
        fails.append(f"{r['policy_served']} policy-served verdicts")
    if not r["armed"]:
        fails.append("failover not armed")
    if r["health"] != "HEALTHY" or r["trips"]:
        fails.append(f"failover health {r['health']}, {r['trips']} trips")
    if r["resources"] != scale.resources:
        fails.append(f"{r['resources']} resources touched")
    if r["rules"] != scale.rules:
        fails.append(f"{r['rules']} flow rules loaded")
    return fails


def run_mesh(scale: Scale, seed: int, n_chips: int) -> dict:
    """The same seeded stream through ``enable_mesh(n_chips)`` and through
    a one-device Engine in this process, verdict for verdict."""
    from dataclasses import replace

    from sentinel_tpu.parallel import make_mesh
    from sentinel_tpu.runtime.engine import Engine
    from sentinel_tpu.utils.clock import ManualClock

    mesh_ids = sorted({d.id for d in make_mesh(n_chips).devices.flat})
    world = build_world(scale, seed)
    one_flush = replace(world, scale=replace(scale, flushes=1))
    clocks, engines = [], []
    for sharded in (False, True):
        clock = ManualClock(start_ms=10_000)
        eng = Engine(clock=clock)
        if sharded:
            eng.enable_mesh(n_chips)
        eng.set_flow_rules(world.flow)
        eng.set_degrade_rules(list(world.degrade.values()))
        eng.set_param_rules({PARAM_RES: [world.param]})
        clocks.append(clock)
        engines.append(eng)
    recs: List[List[FlushRecord]] = [[], []]
    shardings = []
    for k in range(scale.flushes):
        for side in (0, 1):
            # The same generator state on both sides: identical traffic.
            rng = np.random.default_rng(seed + 100 + k)
            run_traffic(engines[side], one_flush, clocks[side], 0, rng,
                        recs[side])
        shardings.append(_state_shardings(engines[1]))
    diff = 0
    for ra, rb in zip(*recs):
        for (_, ga), (_, gb) in zip(ra.groups, rb.groups):
            diff += int(not (np.array_equal(ga.admitted, gb.admitted)
                             and np.array_equal(ga.wait_ms, gb.wait_ms)))
    out = {
        "mesh_devices": mesh_ids,
        "groups": sum(len(r.groups) for r in recs[0]),
        "entries": int(sum(g.n for r in recs[0] for _, g in r.groups)),
        "diff": diff,
        "shardings": shardings,
        "policy_served": sum(policy_served(e) for e in engines),
    }
    for e in engines:
        e.close()
    return out


def _state_shardings(engine) -> dict:
    """Which devices hold each piece of the engine's device state."""
    import jax

    def devs(tree) -> List[int]:
        ids = set()
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "sharding"):
                ids |= {d.id for d in leaf.sharding.device_set}
        return sorted(ids)

    return {
        "stats": devs(engine.stats),
        "flow_table": devs(engine.flow_index.device),
        "degrade_table": devs(engine.degrade_index.device),
        "flow_dyn": devs(engine.flow_dyn),
        "degrade_dyn": devs(engine.degrade_dyn),
        "param_dyn": devs(engine.param_dyn),
    }


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def _devices(n_chips: int):
    """The TPU devices, or exit non-zero (printing no result)."""
    import jax

    from sentinel_tpu.utils.backend import require_platform

    require_platform("tpu")
    devs = jax.devices()
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, found {len(devs)}")
    return devs


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh path against one device")
    args = ap.parse_args(argv)

    devs = _devices(args.chips)
    from sentinel_tpu.utils.backend import enable_compile_cache

    cache_dir = enable_compile_cache()
    clog = CompileLog()
    _log(f"device: {devs[0].device_kind} x{len(devs)}  compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        import __graft_entry__

        r = run_mesh(MESH, args.seed, 4)
        _log(f"mesh devices: {r['mesh_devices']}")
        _log(f"mesh vs one device: groups={r['groups']} entries={r['entries']} "
             f"differing groups={r['diff']}")
        for k, s in enumerate(r["shardings"]):
            _log(f"state devices after mesh flush {k + 1}: {s}")
        __graft_entry__.dryrun_multichip(4)
        fails = []
        if len(r["mesh_devices"]) != 4:
            fails.append(f"mesh spans {r['mesh_devices']}")
        if r["diff"] or r["policy_served"]:
            fails.append(f"{r['diff']} differing groups, "
                         f"{r['policy_served']} policy-served")
        for k, s in enumerate(r["shardings"], start=1):
            if any(ids != r["mesh_devices"] for ids in s.values()):
                fails.append(f"state not replicated on the mesh at flush {k}: {s}")
    else:
        r = run_single(FULL, args.seed)
        _log(f"rules loaded: flow={r['rules']} degrade={r['degrade_rules']} "
             f"param=1 (keys={FULL.param_keys}) in {r['load_s']:.3f} s host")
        _log(f"resources touched: {r['resources']}  stats rows: {r['stats_rows']}")
        _log(f"flushes: depth0={r['flushes'][0]} depth2={r['flushes'][2]} "
             f"entries/flush={FULL.batch} exits/flush<={FULL.exits} "
             f"entries={r['entries']} admitted={r['admitted']} exits={r['exits']}")
        _log("host-clock smoke figures, not device metrics: median submit+flush "
             f"ms depth0={r['host_ms'][0]:.3f} depth2={r['host_ms'][2]:.3f}")
        _log(f"FlowQpsDemo passes per window: {r['demo']}")
        _log(f"oracle: resources={r['sample']} ops={r['checked']} "
             f"mismatches={r['mismatches']}")
        _log(f"failover armed: {r['armed']}  health: {r['health']}  "
             f"trips: {r['trips']}  policy-served verdicts: "
             f"{r['policy_served']}")
        fails = _single_ok(r, FULL)
    _log(clog.line())
    _log(f"longest compiles (s): {clog.longest()}")
    _log(f"peak_bytes_in_use: {peak_bytes(devs[0])}  wall_s: "
         f"{time.perf_counter() - t0:.3f}")
    if fails:
        for f in fails:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
